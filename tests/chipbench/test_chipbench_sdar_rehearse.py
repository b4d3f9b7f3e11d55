"""`sdar_30b_a3b_pp8` and its cell `sdar_decode_gen256` through the harness
at their rehearsal sizes on the CPU (2 layers, a 3 s window): what the
configuration file states against the catalog's row; the `serve_blocks` kind's
set-up, window and replaying comparison with the reference; every per-layer
metric of the cell read off the kind's fixture; the control and each planted
fault coming out NOT correct; the schedule; the work functions against counts
made by hand; and a new reader finding nothing where the program has no such
program, as a parent without the block iteration has not."""
import types

import numpy as np
from chipbench_rehearsal import rehearse

from chipbench import compare, control, manifest
from chipbench.drivers import serve_blocks

CELL_NAME = "sdar_decode_gen256"
MF = manifest.load_manifest()
CELL = manifest.Cell(MF, CELL_NAME)
CONFIG = CELL.config
LIMITS = {**CELL.traffic["limits"], **CELL.traffic["rehearse"]["limits"]}
NEW_METRICS = {"block_device_ms.serve", "block_gap_ms.serve",
               "moe_share.block", "expert_read_roofline.block",
               "block_attention_roofline", "unmask_share.block",
               "mfu.decode_block"}
# the catalog's row (`config` of SDAR-30B-A3B-Chat, model-configs guide)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


# --------------------------------------------------------- the configuration
def test_every_catalog_key_is_at_the_top_as_published_but_the_depth():
    for key, value in PUBLISHED.items():
        want = 6 if key == "num_hidden_layers" else value
        assert CONFIG[key] == want, key             # at the TOP of the file
        assert CONFIG["model"][key] == want, key    # and as the harness runs
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    entry = {c["name"]: c for c in MF["configs"]}["sdar_30b_a3b_pp8"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert {"block_length", "request_defaults", "mask_token_id", "qk_norm",
            "unshifted_logits", "norm_placement"} <= set(CONFIG["assumed"])
    assert CONFIG["model"]["block_length"] == 4
    assert CONFIG["model"]["mask_token_id"] == 151669
    assert "8 pipeline stages of 6 layers" in CONFIG["deployment"]
    assert len(CONFIG["departures"]) == 4
    # 6 divides 48, the period is 1, four layers are the floor
    assert 48 % CONFIG["num_hidden_layers"] == 0
    assert CONFIG["num_hidden_layers"] >= 4


def test_the_cell_is_one_chip_and_names_what_it_reports():
    assert CELL.chips == 1 and CELL.driver == "serve_blocks"
    e2e = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, False)}
    # `gap_p95_ms` spread 3.4% over six windows on the chip (half its bound
    # is 2%) and left the cell's list (PERF.md section 6)
    assert e2e == {"setup_s", "ttft_p50_ms", "ttft_p90_ms"}
    traced = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, True)}
    assert NEW_METRICS <= traced
    assert {"step_device_ms.serve", "step_gap_ms.serve",
            "paged_attention_roofline", "slot_occupancy.serve"}.isdisjoint(
                traced)
    by_name = {m["name"]: m for m in MF["per_layer"]}
    assert {by_name[n]["moves"] for n in NEW_METRICS} == {"ttft_p90_ms"}
    for name in NEW_METRICS:
        spec = CELL.metric_file(name)
        assert manifest.find("reducers", spec["reducer"])
        if "work" in spec:
            assert manifest.find("work", spec["work"])
    mix = CELL.traffic
    assert (mix["max_new"], mix["denoising_steps"],
            mix["confidence_threshold"]) == (256, 2, None)
    assert mix["serve"] == {"decode_slots": 16, "engine_max_len": 2048,
                            "kv_page_size": 16, "prefill_chunk": 256,
                            "paged_kernel": True}


def test_the_schedule_is_the_mixs_and_never_draws_the_mask_token():
    mix = {**CELL.traffic, "rate_rps": 3.0}
    a = serve_blocks.build_schedule(mix, 20.0, 1, 151936, 151669)
    b = serve_blocks.build_schedule(mix, 20.0, 2, 151936, 151669)
    assert [(p.due, len(p.tokens)) for p in a] == [
        (p.due, len(p.tokens)) for p in b]
    assert a[0].tokens != b[0].tokens
    assert all(p.max_new == 256 for p in a) and 50 <= len(a) <= 60
    assert all(32 <= len(p.tokens) <= 1024 for p in a)
    tiny = serve_blocks.build_schedule(
        {**mix, "prompt": {"median": 400, "sigma": 0.1, "min": 300,
                           "max": 500}}, 20.0, 3, 6, 3)
    drawn = {t for p in tiny for t in p.tokens}
    assert drawn == {1, 2, 4, 5}        # [1, vocab) without the mask id


# ------------------------------------------------------------- the rehearsal
def test_a_sound_run_is_correct_through_the_new_driver_kind(capsys):
    rc, obj = rehearse(capsys, CELL_NAME, seed=2345678901, seconds=3.0)
    assert rc == 0 and obj["correct"] is True
    assert obj["device"]["platform"] == "cpu"       # stamped: no result
    assert set(obj["compared"]) == set(LIMITS) == {
        "served_logit_gap", "confidence_gap", "confidence_drift"}
    held = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, False)}
    assert set(obj["metrics"]) == held
    assert obj["failed"] == 0 and obj["attempted"] > 4


def test_a_traced_rehearsal_finds_every_new_metric(capsys):
    rc, obj = rehearse(capsys, CELL_NAME, seed=2147484001, seconds=3.0,
                       trace=1)
    assert rc == 0
    want = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME,
                                                    traced=True)}
    assert NEW_METRICS <= want and set(obj["metrics"]) == want
    assert len(want) == 13
    for name, row in obj["metrics"].items():
        assert row["value"] > 0, name
        if row["unit"] == "%":
            assert row["value"] <= 100, name


def test_the_control_and_every_planted_fault_are_not_correct():
    rows = control.read(CELL_NAME, seed=5, rehearse=True)
    assert set(rows) == {"program", "control_fp8", "fault_token_altered",
                         "fault_block_causal", "fault_uncommitted"}
    ok, _ = compare.judge(rows.pop("program"), LIMITS)
    assert ok
    for case, numbers in rows.items():
        ok, _ = compare.judge(numbers, LIMITS)
        assert not ok, (case, numbers)
        assert numbers["served_logit_gap"] > LIMITS["served_logit_gap"]


def test_the_replay_rebuilds_the_state_before_each_forward():
    """A request of prompt 6 and 10 tokens, block 4, static 2 steps: the
    pass of forward 1 holds, in the noised copy, the prompt's tail and what
    forward 0 unmasked, and the mask token elsewhere; a noised block sees
    the clean blocks before it and itself both ways."""
    driver = serve_blocks.Driver(CELL, 1, True)
    notes = [(0, .5), (1, .5)] + [(0, .5), (0, .5), (1, .5), (1, .5)] * 2
    r = types.SimpleNamespace(
        plan=types.SimpleNamespace(tokens=tuple(range(10, 16))),
        tokens=list(range(20, 30)), notes=notes)
    ids, mask, pos, rows, where = driver.replay_inputs(r, 1)
    mid = driver.model["mask_token_id"]
    # clean: 16 positions (the last block is whole); noised from position 4
    assert list(ids[:16]) == list(range(10, 16)) + list(range(20, 30))
    assert list(ids[16:28]) == [14, 15, 20, mid, 22, 23, mid, mid, 26, 27,
                                mid, mid]
    assert list(pos[16:28]) == list(range(4, 16))
    assert list(rows) == [16 + 3, 16 + 6, 16 + 7, 16 + 10, 16 + 11]
    assert list(where) == [1, 4, 5, 8, 9]
    noised = 16 + (8 - 4)                       # position 8, block 2
    assert mask[noised, :8].all() and not mask[noised, 8:16].any()
    assert list(np.nonzero(mask[noised, 16:28])[0]) == [4, 5, 6, 7]
    assert mask[3, :4].all() and not mask[3, 4:].any()      # clean rows
    _i, causal, *_ = driver.replay_inputs(r, 1, "block_causal")
    assert list(np.nonzero(causal[noised, 16:28])[0]) == [4]
    _i, stale, *_ = driver.replay_inputs(r, 1, "uncommitted")
    assert stale[noised, :4].all() and not stale[noised, 4:16].any()
    assert list(np.nonzero(stale[noised, 16:28])[0]) == list(range(8))


# --------------------------------------------------------------- the readers
def test_the_work_functions_count_from_shapes_and_the_log():
    m = CONFIG["model"]
    read = manifest.find("work", "sdar_expert_read")(
        CELL, {"moe_pairs": 10, "moe_experts_live": 7})
    assert read["flops"] == 10 * 6 * 2048 * 768
    assert read["bytes"] == 7 * 3 * 2048 * 768 * 2 + 10 * (
        2 * 2048 + 3 * 768) * 2
    attn = manifest.find("work", "block_attention_traffic")(
        CELL, {"block_context": 100})
    assert attn["bytes"] == 100 * 6 * 2 * 4 * 128 * 2     # 12,288 B a token
    assert attn["flops"] == 100 * 6 * 4 * (4 * 32) * 128
    flops = manifest.find("work", "sdar_decode_flops")(
        CELL, {"block_positions": 8, "prompt_tokens": 4,
               "context_keys": 50})
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
             + 8 * 3 * 2048 * 768)
    assert flops["flops"] == 2 * 6 * layer * 12 \
        + 2 * 2048 * m["vocab_size"] * 8 + 6 * 4 * 32 * 128 * 50
    # nothing logged (a program without the block iteration): nothing read
    assert manifest.find("work", "sdar_expert_read")(CELL, {}) == {
        "flops": 0.0, "bytes": 0.0}


def test_a_new_reader_finds_nothing_in_a_trace_without_block_programs():
    """The parent has no `_block_all`: laid over it, the new readers return
    None (run.py leaves the metric out) and do not raise."""
    trace = manifest.load_json(
        manifest.HERE / "fixtures" / "serve.plane.json")
    from chipbench import reduce

    ctx = {"cell": CELL, "log": {**trace["log"]}, "peaks": manifest.load_json(
        manifest.HERE / "peaks.json")["TPU v5 lite"],
        "window_s": reduce.window_seconds(trace),
        "busy_s": reduce.busy_seconds(trace)}
    # (the kernel's and the whole step's shares read a dense log as the
    # dense block's work and are listed for this cell alone)
    for name in sorted(NEW_METRICS - {"block_attention_roofline",
                                      "mfu.decode_block"}):
        spec = CELL.metric_file(name)
        got = manifest.find("reducers", spec["reducer"])(spec, trace, ctx)
        assert got is None, name
