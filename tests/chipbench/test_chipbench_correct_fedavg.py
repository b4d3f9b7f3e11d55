"""`correct` in the fedavg kind: true on the sound path, false for the
control (the reference in fp8) and for each fault the cell can have, with the
timed path broken underneath a run that is otherwise whole. Tiny sizes, CPU."""
import jax
import pytest
from chipbench_rehearsal import rehearse

from chipbench import compare, control, manifest

CELL = "resnet18gn_fedavg_c100"
_T = manifest.Cell(manifest.load_manifest(), CELL).traffic
LIMITS = {**_T["limits"], **_T["rehearse"].get("limits", {})}


def test_a_sound_run_is_correct_and_its_line_well_formed(capsys):
    rc, obj = rehearse(capsys, CELL)
    assert rc == 0 and obj["correct"] is True
    assert obj["device"]["platform"] == "cpu"       # stamped: no result
    assert list(obj)[-1] == "compared"
    assert set(obj["compared"]) == set(LIMITS)


def test_a_traced_rehearsal_reads_the_fixture_through_every_reducer(capsys):
    rc, obj = rehearse(capsys, CELL, trace=1)
    assert rc == 0
    assert {"window_s", "busy_s"} <= set(obj["device"])
    assert set(obj["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_control_and_the_planted_fault_are_not_correct():
    rows = control.read(CELL, seed=5, rehearse=True,
                        cases=["control_fp8", "fault_half_batch"])
    for case, numbers in rows.items():
        ok, _ = compare.judge(numbers, LIMITS)
        assert not ok, (case, numbers)


def _state_unchanged(monkeypatch):
    from fedml_tpu.simulation.simulator import Simulator

    real = Simulator.run_round

    def frozen(self, r):
        keep = jax.tree.map(lambda a: a.copy(), self.server_state)
        out = real(self, r)
        self.server_state = keep
        return out

    monkeypatch.setattr(Simulator, "run_round", frozen)


def _half_batch(monkeypatch):
    from fedml_tpu.algorithms import builtin

    real = builtin.make_batch_indices
    monkeypatch.setattr(
        builtin, "make_batch_indices",
        lambda *a, **k: (lambda idx: idx[:, : idx.shape[1] // 2])(
            real(*a, **k)))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch_left_out"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    rc, obj = rehearse(capsys, CELL)
    assert rc == 0 and obj["correct"] is False
    assert any(r["value"] > r["limit"] for r in obj["compared"].values())
