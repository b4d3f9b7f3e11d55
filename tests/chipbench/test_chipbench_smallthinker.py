"""`smallthinker_21b_pp7` and its cell `smallthinker_decode_mixed16k` through
the harness at their rehearsal sizes on the CPU (a global and a window layer,
a 2 s window): what the configuration file states against the catalog's row;
the names resolving through `manifest.find`; the `serve_routed` kind's set-up,
window and comparison with the reference; every per-layer metric of the cell
read off its fixture; the work functions against counts made by hand; and
the new readers finding nothing where the program has no ring, as the
parent's has not."""
import types

import pytest
from chipbench_rehearsal import rehearse

from chipbench import manifest, reduce
from chipbench.drivers import serve_routed

CELL_NAME = "smallthinker_decode_mixed16k"
MF = manifest.load_manifest()
CELL = manifest.Cell(MF, CELL_NAME)
CONFIG = CELL.config
NEW_METRICS = {"window_attention_roofline", "window_key_share.serve",
               "mfu.decode_window"}
GLOBAL, WINDOW = 0, 1
# the catalog's row (`config` of SmallThinker-21BA3B-Instruct, model-configs
# guide): two periods of G W W W spelled out, then the whole 52
LAYOUT = [GLOBAL, WINDOW, WINDOW, WINDOW] * 13
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 8, "rope_layout": LAYOUT[:8],
           "sliding_window_layout": LAYOUT[:8]}


# --------------------------------------------------------- the configuration
def test_every_catalog_key_is_at_the_top_as_published_but_the_cut():
    for key, value in PUBLISHED.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want, key             # at the TOP of the file
        assert CONFIG["model"][key] == want, key    # and as the harness runs
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in REDUCED}
    entry = {c["name"]: c for c in MF["configs"]}["smallthinker_21b_pp7"]
    assert entry["reduced"] == sorted(REDUCED)
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert {"router_input", "expert_activation", "qk_norm", "window",
            "routing"} <= set(CONFIG["assumed"])
    assert "7 pipeline stages" in CONFIG["deployment"]
    assert "secondary" in CONFIG["departures"][0]
    # two whole periods (at least the floor of four layers)
    n = CONFIG["num_hidden_layers"]
    assert n % 4 == 0 and n >= 4


def test_the_names_resolve_and_the_module_is_the_configurations():
    assert CELL.reference().__name__ == \
        "chipbench.reference.smallthinker_21b_pp7"
    assert manifest.find("drivers", CELL.driver) is serve_routed.Driver
    lm, spec = manifest.find("models", "smallthinker")(CONFIG["model"])
    assert spec["model_kind"] == "lm" and spec["lm"]["rope_full"] is False
    assert [a for a, _ in lm.kinds] == ["full", "window", "window",
                                        "window"] * 2
    assert (lm.n_heads, lm.n_kv_heads, lm.head_dim, lm.window) == (
        28, 4, 128, 4096)
    assert (lm.moe.n_experts, lm.moe.top_k, lm.moe.d_expert, lm.moe.act,
            lm.moe.route_from, lm.moe.n_shared) == (
                64, 6, 768, "reglu", "attn", 0)
    with pytest.raises(ValueError, match="rope_layout"):
        manifest.find("models", "smallthinker")(
            {**CONFIG["model"], "rope_layout": [1] * 8})


def test_the_cell_is_one_chip_and_names_what_it_reports():
    assert CELL.chips == 1 and CELL.driver == "serve_routed"
    e2e = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, False)}
    # six untraced windows on the chip spread each under half its bound
    # (PERF.md section 2)
    assert e2e == {"setup_s", "ttft_p50_ms", "ttft_p90_ms", "gap_p95_ms"}
    traced = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, True)}
    assert NEW_METRICS <= traced
    by_name = {m["name"]: m for m in MF["per_layer"]}
    assert by_name["window_attention_roofline"]["moves"] == by_name[
        "mfu.decode_window"]["moves"] == "gap_p95_ms"
    # the dense block's kernel roofline counts every head a KV head
    assert "paged_attention_roofline" not in traced
    mix = CELL.traffic
    assert (mix["prompt"], mix["output"]) == (
        {"median": 4096, "sigma": 1.0, "min": 256, "max": 15360},
        {"median": 192, "sigma": 0.7, "min": 32, "max": 1024})
    assert mix["serve"]["decode_slots"] == 32
    assert mix["serve"]["engine_max_len"] == CONFIG[
        "max_position_embeddings"]
    assert mix["serve"]["prefix_cache"] is False
    assert (mix["schedule_seed"], mix["routing_seed"]) == (401, 21)


# -------------------------------------------------------- work and readers
def fake_cell(model):
    return types.SimpleNamespace(config={"model": model}, traffic={})


def test_the_window_kernels_work_by_hand():
    m = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
         "sliding_window_layout": [0, 1, 1, 1]}
    got = manifest.find("work", "window_attention_traffic")(
        fake_cell(m), {"window_step_keys": 10})
    # 3 window layers x 10 keys: K and V of 2 heads of 16, 2 bytes; the
    # scores and weighted sums of 4 query heads
    assert got == {"flops": 4.0 * 4 * 16 * 30, "bytes": 2.0 * 2 * 16 * 2 * 30}


def test_the_whole_steps_work_by_hand():
    m = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 4,
         "sliding_window_layout": [0, 1, 1, 1], "moe_num_primary_experts": 4,
         "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 3,
         "vocab_size": 10}
    log = {"slot_steps": 5, "prompt_tokens": 7, "admitted": 1,
           "context_keys": 100, "window_keys": 40}
    per_position = 2 * 8 * 8 + 2 * 8 * 4 + 8 * 4 + 2 * 3 * 8 * 3
    want = (2 * 4 * per_position * 12 + 2 * 8 * 10 * 6
            + 4 * 2 * 4 * (1 * 100 + 3 * 40))
    got = manifest.find("work", "smallthinker_decode_flops")(
        fake_cell(m), log)
    assert got == {"flops": want, "bytes": 0.0}


def test_a_reader_finds_nothing_where_the_program_has_no_ring():
    """The parent's programs have no window kernel and no such counters:
    the new readers return nothing, and the line leaves them out."""
    plane = manifest.load_json(manifest.HERE / "fixtures" / "serve.plane.json")
    ctx = {"cell": CELL, "log": plane["log"], "peaks": {
        "bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
        "window_s": reduce.window_seconds(plane),
        "busy_s": reduce.busy_seconds(plane)}
    for name in ("window_attention_roofline", "window_key_share.serve"):
        spec = CELL.metric_file(name)
        assert manifest.find("reducers", spec["reducer"])(
            spec, plane, ctx) is None


# ------------------------------------------------- the rehearsal, end to end
def test_a_traced_rehearsal_is_correct_and_reads_every_metric(capsys):
    rc, obj = rehearse(capsys, CELL_NAME, seed=2147483714, seconds=2.0,
                       trace=1)
    want = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME, True)}
    assert rc == 0 and obj["correct"] is True
    assert set(obj["compared"]) == {"served_logit_gap",
                                    "served_logit_drift"}
    assert obj["attempted"] > 0 and obj["failed"] == 0
    assert set(obj["metrics"]) == want
    for name in ("window_attention_roofline", "mfu.decode_window"):
        assert 0 < obj["metrics"][name]["value"] <= 100
    assert any(n == "decode.attn:paged_attention_window"
               for n, _ in obj["breakdown"]["device_ops"])
