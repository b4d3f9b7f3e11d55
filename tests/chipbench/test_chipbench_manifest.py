"""BENCHMARK.json and the data files keep to the contract's names, units and
lengths; every `moves` points at an end-to-end metric that every listed cell
reports; every cell's files exist and name code that exists."""
import json
import re

import pytest

from chipbench import manifest

MF = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MF["workloads"]]
METRICS = MF["end_to_end"] + MF["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(MF) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(MF["run_seconds"], int) and 1 <= MF["run_seconds"] <= 51
    assert 1 <= len(MF["paths"]) <= 16 and len(MF["command"]) <= 32
    assert len(json.dumps(MF)) <= 64 * 1024
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MF["end_to_end"])
    assert sum(w["chips"] == 4 for w in MF["workloads"]) <= max(
        1, len(CELLS) // 4)


@pytest.mark.parametrize("entry", MF["configs"] + MF["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            v = entry[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v
    for key in entry.get("reduced", []):
        assert NAME.match(key)


def test_no_name_twice():
    for group in (MF["configs"], MF["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MF["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entries_have_exactly_the_contracts_keys():
    for m in MF["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MF["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for w in MF["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for c in MF["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}


@pytest.mark.parametrize("metric", MF["per_layer"], ids=lambda m: m["name"])
def test_moves_names_a_metric_every_listed_cell_reports(metric):
    e2e = {m["name"]: m for m in MF["end_to_end"]}
    assert metric["moves"] in e2e and metric["moves"] != "setup_s"
    moved = e2e[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert manifest.applies(moved, cell), (metric["name"], cell)
    layers = {}
    for m in MF["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files_and_reports_enough(cell):
    c = manifest.Cell(MF, cell)
    # a driver kind is a file: drivers/<kind>.py, with the rehearsal's trace
    assert (manifest.HERE / "drivers" / f"{c.driver}.py").exists()
    assert (manifest.HERE / "fixtures" / f"{c.driver}.plane.json").exists()
    driver = manifest.find("drivers", c.driver)(c, 1, True)
    for method in ("setup", "window", "check", "controls", "programs"):
        assert callable(getattr(driver, method)), (c.driver, method)
    assert isinstance(driver.states, tuple)
    assert driver.traffic["limits"], "a cell compares nothing"
    assert (manifest.HERE / "reference" / f"{c.config_name}.py").exists()
    assert c.reference().__name__ == f"chipbench.reference.{c.config_name}"
    if "model_type" in c.config["model"]:       # the program's model: a file
        assert callable(manifest.find(
            "models", c.config["model"]["model_type"]))
    assert c.traffic["limits"], "a cell compares nothing"
    assert "rehearse" in c.traffic and "rehearse" in c.config
    e2e = [m["name"] for m in manifest.metrics_for(MF, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(MF, cell, True)
    for cfg in MF["configs"]:
        path = manifest.ROOT / cfg["file"]
        assert path.exists() and any(
            cfg["file"].startswith(p + "/") for p in MF["paths"])
        held = json.loads(path.read_text())
        assert held["source"] == cfg["source"]
        # a cut sits at the top of the file or in its `model` group, with
        # the value the source publishes stated beside it
        for key in cfg["reduced"]:
            assert key in held or key in held["model"], (cfg["name"], key)
            assert key in held["published"], (cfg["name"], key)
    assert {w["config"] for w in MF["workloads"]} == {
        c["name"] for c in MF["configs"]}


@pytest.mark.parametrize("metric", MF["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_of_its_own(metric):
    spec = manifest.load_json(
        manifest.HERE / "metrics" / f"{metric['name']}.json")
    assert callable(manifest.find("reducers", spec["reducer"]))
    if "work" in spec:
        assert callable(manifest.find("work", spec["work"]))
    for pat in spec.get("programs", []) + spec.get("kernels", []):
        re.compile(pat)
    if "roofline" in metric["name"]:
        assert metric["unit"] == "%" and spec["reducer"] == "kernel_roofline"
        # a roofline rides beside the whole step's mfu moving the same metric
        assert any("mfu" in m["name"] and m["moves"] == metric["moves"]
                   and set(metric["workloads"]) <= set(m["workloads"])
                   for m in MF["per_layer"])


def test_files_under_paths_keep_to_the_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MF["paths"]:
        for f in (manifest.ROOT / p).rglob("*"):
            rel = f.relative_to(manifest.ROOT).as_posix()
            if "__pycache__" in rel or "/out/" in rel:
                continue
            assert ok.match(rel), rel
    for mix in (manifest.HERE / "traffic").iterdir():
        assert mix.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
