"""`kexaone_fedlora_s4x8k` through the harness at its rehearsal sizes on the
CPU: the `fedlora_moe` kind's window and its comparison with the reference
(federated LoRA's first aggregated update and the change after three rounds),
every per-layer metric of the cell read off the kind's fixture, and the
control and each planted fault coming out NOT correct."""
import pytest
from chipbench_rehearsal import rehearse

from chipbench import compare, control, manifest

CELL = "kexaone_fedlora_s4x8k"
MF = manifest.load_manifest()
_T = manifest.Cell(MF, CELL).traffic
LIMITS = {**_T["limits"], **_T["rehearse"].get("limits", {})}


def test_a_sound_run_is_correct_through_the_new_driver_kind(capsys):
    rc, obj = rehearse(capsys, CELL)
    assert rc == 0 and obj["correct"] is True
    assert obj["device"]["platform"] == "cpu"       # stamped: no result
    assert set(obj["compared"]) == set(LIMITS) == {
        "grad1_gap", "change_gap", "grad1_median_gap", "change_median_gap"}
    assert set(obj["metrics"]) == {"setup_s", "train_tok_s"}
    assert obj["failed"] == 0


def test_a_traced_rehearsal_finds_every_new_metric(capsys):
    rc, obj = rehearse(capsys, CELL, trace=1)
    assert rc == 0
    want = {m["name"] for m in manifest.metrics_for(MF, CELL, traced=True)}
    assert set(obj["metrics"]) == want and len(want) == 10
    for name, row in obj["metrics"].items():
        if row["unit"] == "%":
            assert 0 < row["value"] <= 100, name
    assert {"window_s", "busy_s"} <= set(obj["device"])
    scopes = {k.split(":")[0] for k, _ in obj["breakdown"]["device_ops"]}
    assert any(s.startswith("moe.") for s in scopes)


def test_the_control_and_every_planted_fault_are_not_correct():
    rows = control.read(CELL, seed=5, rehearse=True)
    assert set(rows) == {"control_fp8", "fault_half_silos",
                         "fault_drop_expert", "fault_unnormalised"}
    for case, numbers in rows.items():
        ok, _ = compare.judge(numbers, LIMITS)
        assert not ok, (case, numbers)


def test_the_window_sums_the_programs_own_counts_into_the_log():
    cell = manifest.Cell(MF, CELL)
    driver = manifest.find("drivers", cell.driver)(cell, 7, True)
    driver.counted = [(0, {"moe_pairs": 10.0, "moe_max_rows": 4.0}),   # settle
                      (0, {"moe_pairs": 11.0, "moe_max_rows": 5.0}),
                      (1, {"moe_pairs": 12.0, "moe_max_rows": 6.0}),
                      (2, {"moe_pairs": 13.0, "moe_max_rows": 7.0})]   # after
    log = driver.work_log(2)
    assert log["moe_pairs"] == 23.0 and log["moe_max_rows"] == 11.0
    assert log["tokens"] == 2 * driver.units_per_round()


def test_the_seed_draws_the_weights_but_not_the_routers():
    """Which experts are popular decides how many pairs this share computes:
    the routers' leaves come from the traffic file's `routing_seed`, every
    other weight and the token ids from --seed."""
    import jax
    import numpy as np

    from chipbench import inputs

    cell = manifest.Cell(MF, CELL)
    trees = []
    for seed in (7, 8):
        driver = manifest.find("drivers", cell.driver)(cell, seed, True)
        driver.build()
        flat = jax.tree_util.tree_flatten_with_path(driver.base())[0]
        trees.append({inputs.path_str(p): np.asarray(a) for p, a in flat})
        driver.free()
    routing = [k for k in trees[0]
               if any(name in k for name in _T["routing_leaves"])]
    assert len(routing) == 2 * 3       # a kernel and a bias a sparse layer
    for k in trees[0]:
        same = np.array_equal(trees[0][k], trees[1][k])
        assert same == (k in routing), k
