"""BENCHMARK.json and everything it names, found by name.

A configuration is `configs/<name>.json` (the manifest gives the path), a
traffic mix `traffic/<name>.json`, a per-layer metric `metrics/<name>.json`.
The CODE those files name is found the same way, by `find(kind, name)`:

    kind       named by                    file                    it exports
    drivers    a traffic file's `driver`   drivers/<kind>.py       `Driver`
    models     a model's `model_type`      models/<model_type>.py  `build`
    reference  the configuration's name    reference/<config>.py   the module
    work       a metric file's `work`      work/<name>.py          `<name>`
    reducers   a metric file's `reducer`   reducers/<kind>.py      `<kind>`

(`build(model, **options)`, `<name>(cell, log)`, `<kind>(spec, trace, ctx)`)
so adding one is adding a file. The work functions and reducers that came
with the harness live together in their directory's `__init__.py` (its
`__all__` says which of its functions are of the kind) and are found there
under the same rule: the directory is the kind's one home.
Nothing here knows a cell, a configuration, a metric or a function by name.
"""
from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# what a file of each kind exports: a fixed attribute, the module itself
# (None), or a function of the file's own name ("")
EXPORTS = {"drivers": "Driver", "models": "build", "reference": None,
           "work": "", "reducers": ""}
CODE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def find(kind: str, name: str):
    """The code a data file names: `chipbench/<kind>/<name>.py`'s export,
    or the function `<name>` that `chipbench/<kind>/__init__.py` holds. A
    name that resolves to nothing is a KeyError that names the file looked
    for."""
    if kind not in EXPORTS:
        raise KeyError(f"no such kind of code {kind!r}; there are "
                       f"{sorted(EXPORTS)}")
    if not CODE_NAME.match(str(name)):
        raise KeyError(f"{kind} {name!r} is no name a file can have")
    export = EXPORTS[kind]
    path = HERE / kind / f"{name}.py"
    if path.exists():
        module = importlib.import_module(f"chipbench.{kind}.{name}")
        found = module if export is None else getattr(
            module, export or name, None)
        if found is None:
            raise KeyError(f"chipbench/{kind}/{name}.py defines no "
                           f"{export or name!r}")
        return found
    home = importlib.import_module(f"chipbench.{kind}")
    if export == "" and name in home.__all__:
        return getattr(home, name)
    raise KeyError(
        f"{kind} {name!r}: no file chipbench/{kind}/{name}.py" + (
            f", and chipbench/{kind}/__init__.py has no function of that name"
            if export == "" else ""))


def names(kind: str) -> list[str]:
    """Every name `find(kind, ...)` resolves: the directory's files and, of
    the kinds that are functions, those its `__init__.py` defines."""
    found = {f.stem for f in (HERE / kind).glob("*.py")
             if f.stem != "__init__"}
    if EXPORTS[kind] == "":
        found |= set(importlib.import_module(f"chipbench.{kind}").__all__)
    return sorted(found)


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def applies(metric: dict, workload: str) -> bool:
    """A metric without a `workloads` key is reported by every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def metrics_for(manifest: dict, workload: str, traced: bool) -> list[dict]:
    """The manifest's metrics this cell reports in this mode."""
    group = manifest["per_layer"] if traced else manifest["end_to_end"]
    if not traced:
        return [m for m in group if applies(m, workload)]
    # a per-layer metric rides the cells that report the metric it moves
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    return [m for m in group if applies(m, workload)
            and applies(e2e[m["moves"]], workload)]


class Cell:
    """One entry of `workloads` with its three kinds of data file loaded."""

    def __init__(self, manifest: dict, workload: str, root: Path = ROOT):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if workload not in by_name:
            raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json "
                           f"has {sorted(by_name)}")
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        self.config_name = cfg["name"]
        self.config = load_json(root / cfg["file"])
        self.traffic = load_json(
            HERE / "traffic" / f"{self.entry['traffic']}.json")
        self.driver = self.traffic["driver"]

    def sizes(self, rehearse: bool) -> tuple[dict, dict, dict]:
        """(traffic, configuration, its model group) as a run uses them:
        the files' own values, or with their `rehearse` blocks laid over
        them for a CPU rehearsal at tiny sizes."""
        traffic, config = dict(self.traffic), dict(self.config)
        model = dict(config["model"])
        if rehearse:
            traffic.update(self.traffic.get("rehearse", {}))
            over = dict(self.config.get("rehearse", {}))
            model.update(over.pop("model", {}))
            config.update(over)
        return traffic, config, model

    def reference(self):
        """The configuration's plain reference: `reference/<config>.py`."""
        return find("reference", self.config_name)

    def metric_file(self, name: str) -> dict:
        return load_json(HERE / "metrics" / f"{name}.json")
