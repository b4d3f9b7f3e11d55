"""One driver per KIND of cell: the traffic file names the kind, and
`manifest.find("drivers", kind)` finds `drivers/<kind>.py`'s `Driver`. A later
cell of an existing kind brings data files only; a new kind brings its file
here (it may subclass one that is there) and `fixtures/<kind>.plane.json`
for the rehearsal. A kind knows its program's entry points, never a model:
the model comes from `manifest.find("models", model["model_type"])`.

What chipbench/run.py asks of a driver: `Driver(cell, seed, rehearse)` with
`traffic` (its `limits` are what `correct` holds) and `log` (what the work
functions read of the traced window); `setup()`, `window(seconds, tracer)`
-> {"attempted", "failed", "metrics"}, `check()` -> the numbers compared
(frees the program first); `controls(cases)` for chipbench/control.py; and
the two below, which a kind overrides where it has something to say."""
from __future__ import annotations


class Base:
    # spans that say what state a request is in, not what a thread is
    # doing: the breakdown names no idle gap after them
    states: tuple = ()

    def programs(self) -> list:
        """(name, jitted, arguments) of the programs the window drives:
        their compiled text gives the trace its scope paths."""
        return []
