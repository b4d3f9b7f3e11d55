"""The last-line validator: accepts a good object of either mode and refuses
each malformation the driver's reason for refusing PR 22 names."""
import copy
import json
import math

import pytest

from chipbench import lastline, manifest

MF = manifest.load_manifest()
CELL = MF["workloads"][0]["name"]


def good(traced: bool) -> dict:
    metrics = {m["name"]: {"value": 12.5, "unit": m["unit"]}
               for m in manifest.metrics_for(MF, CELL, traced)}
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5_000_000_000}
    obj = {"correct": True, "attempted": 40, "failed": 0, "metrics": metrics,
           "device": dev}
    if traced:
        dev.update(window_s=4.0, busy_s=3.5)
        obj["breakdown"] = {"device_ops": [["fusion", 1.5]],
                            "idle_gaps": [["round", 0.2]]}
    return obj


def first_metric(traced: bool) -> str:
    return manifest.metrics_for(MF, CELL, traced)[0]["name"]


@pytest.mark.parametrize("traced", [False, True])
def test_accepts_a_good_object(traced):
    assert lastline.problems(good(traced), MF, CELL, traced) == []


@pytest.mark.parametrize("cell", [w["name"] for w in MF["workloads"]])
@pytest.mark.parametrize("traced", [False, True])
def test_a_good_object_of_every_cell_round_trips_json(cell, traced):
    metrics = {m["name"]: {"value": 1.0, "unit": m["unit"]}
               for m in manifest.metrics_for(MF, cell, traced)}
    assert metrics, f"{cell} reports nothing in this mode"
    obj = good(traced)
    obj["metrics"] = metrics
    assert lastline.problems(json.loads(json.dumps(obj)), MF, cell,
                             traced) == []


def _drop_metric(o, traced):
    del o["metrics"][first_metric(traced)]


def _wrong_unit(o, traced):
    o["metrics"][first_metric(traced)]["unit"] = "furlongs"


def _nan(o, traced):
    o["metrics"][first_metric(traced)]["value"] = math.nan


def _share_over_100(o, traced):
    share = next(m["name"] for m in manifest.metrics_for(MF, CELL, True)
                 if lastline.is_share(m))
    o["metrics"][share]["value"] = 100.5


BROKEN = {
    "a metric missing": (False, _drop_metric),
    "a per-layer metric absent in a traced run": (True, _drop_metric),
    "a unit that differs from the manifest": (False, _wrong_unit),
    "NaN": (True, _nan),
    "busy_s of 0": (True, lambda o, t: o["device"].update(busy_s=0.0)),
    "busy_s above window_s": (True, lambda o, t: o["device"].update(busy_s=4.5)),
    "window_s missing": (True, lambda o, t: o["device"].pop("window_s")),
    "a missing memory_peak_bytes": (
        False, lambda o, t: o["device"].pop("memory_peak_bytes")),
    "a share over 100%": (True, _share_over_100),
    "a required key missing": (False, lambda o, t: o.pop("failed")),
    "correct not a boolean": (False, lambda o, t: o.update(correct="yes")),
    "failed above attempted": (False, lambda o, t: o.update(failed=41)),
    "a metric of another cell's": (
        False, lambda o, t: o["metrics"].update(
            bogus_ms={"value": 1.0, "unit": "ms"})),
    "a cpu platform as a result": (
        False, lambda o, t: o["device"].update(platform="cpu")),
    "another device count than the cell's": (
        False, lambda o, t: o["device"].update(count=4)),
    "a breakdown of eleven rows": (True, lambda o, t: o["breakdown"].update(
        device_ops=[["x", 1.0]] * 11)),
    "an end-to-end metric of 0": (False, lambda o, t: o["metrics"][
        first_metric(False)].update(value=0.0)),
}


@pytest.mark.parametrize("what", sorted(BROKEN))
def test_refuses(what):
    traced, breaker = BROKEN[what]
    obj = copy.deepcopy(good(traced))
    breaker(obj, traced)
    assert lastline.problems(obj, MF, CELL, traced), what


@pytest.mark.parametrize("metric", MF["per_layer"], ids=lambda m: m["name"])
def test_a_traced_line_may_lack_no_per_layer_metric_of_its_cell(metric):
    """A reader that finds nothing leaves its metric out of the line, and the
    line is then refused by name: a kernel, a scope, a span or a counter that
    was renamed must not run on out of its reader's sight with exit 0."""
    for cell in metric["workloads"]:
        obj = good(True)
        obj["metrics"] = {
            m["name"]: {"value": 1.0, "unit": m["unit"]}
            for m in manifest.metrics_for(MF, cell, True)
            if m["name"] != metric["name"]}
        why = lastline.problems(obj, MF, cell, True)
        assert len(why) == 1 and repr(metric["name"]) in why[0], why


def test_an_untraced_line_may_lack_nothing():
    for cell in (w["name"] for w in MF["workloads"]):
        for m in manifest.metrics_for(MF, cell, False):
            obj = good(False)
            obj["metrics"] = {
                k["name"]: {"value": 1.0, "unit": k["unit"]}
                for k in manifest.metrics_for(MF, cell, False) if k is not m}
            assert lastline.problems(obj, MF, cell, False), (cell, m["name"])


def test_a_rehearsal_line_is_checked_for_form_only():
    obj = good(False)
    obj["device"].update(platform="cpu", kind="cpu")
    assert lastline.problems(obj, MF, CELL, False, result=False) == []
    assert lastline.problems(obj, MF, CELL, False, result=True)


def test_not_an_object():
    assert lastline.problems([1, 2], MF, CELL, False)
