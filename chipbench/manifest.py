"""BENCHMARK.json and the data files it names, found by name.

A configuration is `configs/<name>.json` (the manifest gives the path), a
traffic mix `traffic/<name>.json`, a per-layer metric `metrics/<name>.json`.
Nothing here knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


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
        return importlib.import_module(
            f"chipbench.reference.{self.config_name}")

    def metric_file(self, name: str) -> dict:
        return load_json(HERE / "metrics" / f"{name}.json")
