"""The training comparison by hand: gaps of norms by the worst and by the
median leaf, measured against the larger of the leaf's and the median leaf's
reference norm; still leaves left out of the change; NaN never passes."""
import math

import pytest

from chipbench import compare

REF = {"loss": [2.0, 1.0, 0.5],
       "grad1": {"a": 1.0, "b": 4.0, "c": 1e-6, "d": 2.0, "e": 3.0},
       "change": {"a": 1.0, "b": 4.0, "c": 5.0, "d": 2.0, "e": 3.0}}


def got(**over):
    g = {k: (list(v) if isinstance(v, list) else dict(v))
         for k, v in REF.items()}
    for key, leaves in over.items():
        g[key].update(leaves)
    return g


def test_equal_runs_read_zero():
    n = compare.training_numbers(got(), REF)
    shown = {k: v for k, v in n.items() if not k.startswith("_")}
    assert set(shown) == {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_gap",
                          "grad1_median_gap", "change_gap",
                          "change_median_gap"}
    assert all(v == 0 for v in shown.values())


def test_worst_and_median_leaf_by_hand():
    # median reference grad norm is 2.0: leaf a (norm 1) is measured against
    # 2.0, leaf b (norm 4) against itself, the all-but-zero c against 2.0
    g = got(grad1={"a": 1.5, "b": 4.4, "c": 0.02})
    n = compare.training_numbers(g, REF)
    assert n["grad1_gap"] == pytest.approx(0.25) and n["_grad1_leaf"] == "a"
    # the five gaps: .25, .1, ~.01, 0, 0 -> median ~.01
    assert n["grad1_median_gap"] == pytest.approx((0.02 - 1e-6) / 2.0)


def test_a_leaf_whose_reference_gradient_is_nought_is_left_out_of_the_change():
    g = got(change={"c": 50.0, "b": 4.2})
    n = compare.training_numbers(g, REF)
    assert n["_leaves_left_out"] == ["c"]       # 1e-6 < 2.0 / 1000
    assert n["change_gap"] == pytest.approx(0.05) and n["_change_leaf"] == "b"


def test_loss_gaps_are_relative_to_the_reference():
    g = got()
    g["loss"] = [2.2, 1.0, 0.45]
    n = compare.training_numbers(g, REF)
    assert (n["loss1_gap"], n["loss2_gap"], n["loss3_gap"]) == pytest.approx(
        (0.1, 0.0, 0.1))


def test_a_state_left_unchanged_reads_one():
    zero = {k: 0.0 for k in REF["grad1"]}
    n = compare.training_numbers(got(grad1=zero, change=zero), REF)
    assert n["grad1_gap"] == 1.0 and n["change_gap"] == 1.0
    assert n["grad1_median_gap"] == 1.0


def test_nan_is_the_worst_and_never_passes():
    n = compare.training_numbers(got(grad1={"d": math.nan}), REF)
    assert math.isnan(n["grad1_gap"]) and math.isnan(n["grad1_median_gap"])
    ok, rows = compare.judge(n, {"grad1_gap": 0.5, "loss1_gap": 0.5})
    assert not ok and set(rows) == {"grad1_gap", "loss1_gap"}


def test_judge_holds_only_the_numbers_that_have_a_limit():
    ok, rows = compare.judge({"x": 0.1, "y": 9.0, "_note": "n"}, {"x": 0.2})
    assert ok and rows == {"x": {"value": 0.1, "limit": 0.2}}
    assert not compare.judge({"x": 0.3}, {"x": 0.2})[0]
