"""One driver per KIND of cell (`fedavg`, `fedlora`, `serve`): the traffic
file names the kind, `load` finds the module. A later cell of an existing
kind brings data files only."""
from __future__ import annotations

import importlib


def load(kind: str):
    return importlib.import_module(f"chipbench.drivers.{kind}").Driver
