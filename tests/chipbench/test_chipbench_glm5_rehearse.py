"""`glm5_decode_docqa` through the harness at its rehearsal sizes on the CPU:
the `serve_docs` kind's set-up (warm-up, lead-in), window and comparison with
the reference (served tokens' logits, and the program's selection against the
reference's), every per-layer metric of the cell read off the kind's fixture,
and the control and each planted fault coming out NOT correct."""
import numpy as np
from chipbench_rehearsal import rehearse

from chipbench import compare, control, manifest
from chipbench.trace import Tracer

CELL_NAME = "glm5_decode_docqa"
MF = manifest.load_manifest()
_T = manifest.Cell(MF, CELL_NAME).traffic
LIMITS = {**_T["limits"], **_T["rehearse"]["limits"]}
NEW_METRICS = {"mfu.decode_latent", "latent_attention_roofline",
               "index_scores_roofline", "index_share.serve",
               "moe_share.serve", "selected_share.serve",
               "prefix_hit_share.serve", "admit_chunk_ms.serve",
               "index_share.prefill", "attn_share.prefill",
               "moe_share.prefill"}


def test_a_sound_run_is_correct_through_the_new_driver_kind(capsys):
    rc, obj = rehearse(capsys, CELL_NAME, seconds=3.0)
    assert rc == 0 and obj["correct"] is True
    assert obj["device"]["platform"] == "cpu"       # stamped: no result
    assert set(obj["compared"]) == set(LIMITS) == {
        "served_logit_gap", "selection_miss", "newest_miss"}
    # the first-token median is not held in this cell (PERF.md section 6)
    assert set(obj["metrics"]) == {"setup_s", "ttft_p90_ms", "gap_p95_ms"}
    assert obj["failed"] == 0 and obj["attempted"] > 4


def test_a_traced_rehearsal_finds_every_new_metric(capsys):
    rc, obj = rehearse(capsys, CELL_NAME, seed=2147484001, seconds=3.0,
                       trace=1)
    assert rc == 0
    want = {m["name"] for m in manifest.metrics_for(MF, CELL_NAME,
                                                    traced=True)}
    assert NEW_METRICS <= want and set(obj["metrics"]) == want
    assert len(want) == 19
    for name, row in obj["metrics"].items():
        if row["unit"] == "%":
            assert 0 < row["value"] <= 100, name


def test_the_control_and_every_planted_fault_are_not_correct():
    rows = control.read(CELL_NAME, seed=5, rehearse=True)
    assert set(rows) == {"program", "control_fp8", "fault_token_altered",
                         "fault_selection_ignored", "fault_stale_index"}
    ok, _ = compare.judge(rows.pop("program"), LIMITS)
    assert ok
    for case, numbers in rows.items():
        ok, _ = compare.judge(numbers, LIMITS)
        assert not ok, (case, numbers)
    assert rows["fault_selection_ignored"]["selection_miss"] > LIMITS[
        "selection_miss"]
    assert rows["fault_stale_index"]["selection_miss"] > LIMITS[
        "selection_miss"]
    assert rows["fault_stale_index"]["newest_miss"] == 1.0


def test_a_key_page_the_engine_left_unwritten_is_not_correct():
    """The program's side of the selection reads the ENGINE's pool: zero the
    indexer's keys of every sampled prompt's last whole page where the
    engine holds them, after the window, and the run is not correct, by the
    number that reads the newest pages, though every token it served stands."""
    cell = manifest.Cell(MF, CELL_NAME)
    driver = manifest.find("drivers", cell.driver)(cell, 2147484001, True)
    driver.setup()
    driver.window(3.0, Tracer("", 0.0, on=False))
    eng = driver.runner.predictor.engine
    last = [eng._prefix.get(eng._prefix_lookup(list(r.plan.tokens))[0][-1])
            for r in driver.sample()]
    pages = np.asarray([e.page for e in last if e is not None])
    assert pages.size
    cache = eng._carry["cache"]
    eng._carry = {**eng._carry, "cache": {
        **cache, "ik": cache["ik"].at[:, pages].set(0)}}
    numbers = driver.check()
    ok, _ = compare.judge(numbers, LIMITS)
    assert not ok
    assert numbers["newest_miss"] > LIMITS["newest_miss"]
    assert numbers["served_logit_gap"] <= LIMITS["served_logit_gap"]
