"""The traced part of a window: `jax.profiler` around a few seconds of it,
with the harness's own spans written into the same trace, and what the
PROGRAM says of itself gathered beside it for chipbench/reduce.py:

- named scopes inside the device programs: the v5e trace itself carries no
  path (an operation event has three timing stats and is named by HLO text
  without `metadata=`), so the paths come from the compiled module's text,
  mapped by instruction name (`compiled_scopes`);
- the recorder's host spans (`fedml_tpu.utils.events.recorder.spans`), put
  on the device's timebase with one anchor: the `chipbench.window`
  annotation's start against `perf_counter` at `Tracer.open()`
  (`program_rows`; `anchor_error_us` measures how good the anchor is);
- the program's counters (`fedml_tpu.utils.metrics.snapshot()`), at
  `Tracer.open()` and `Tracer.stop()`: `Tracer.counters` holds the deltas.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import time
from pathlib import Path

from chipbench.reduce import HOST_PREFIX, WINDOW_SPAN

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]*)"')


class Tracer:
    """`on=False` is the untraced run: every method is then a no-op."""

    def __init__(self, out_dir: Path, seconds: float, on: bool):
        self.dir, self.seconds, self.on = Path(out_dir), seconds, on
        self.active = False
        self.done = False
        self._window = None
        # `anchor`: perf_counter at open(), the host-clock reading that
        # belongs to the window annotation's start, good to `bracket_s`
        self.anchor = self.bracket_s = 0.0
        self.counters: dict = {}

    @staticmethod
    def _counters() -> dict:
        from fedml_tpu.utils import metrics

        return dict(metrics.snapshot()["counters"])

    def start(self) -> None:
        """Start the profiler. The window span opens later (`open`), once
        the caller has let the first work after the start go by: the first
        execution after `start_trace` can stall for seconds (seen once in
        three traced runs on the v5e, PERF.md section 6)."""
        if self.on:
            import jax

            jax.profiler.start_trace(str(self.dir))

    def open(self) -> None:
        if not self.on:
            return
        import jax

        self.counters = self._counters()
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        a = time.perf_counter()
        self._window.__enter__()
        b = time.perf_counter()
        # the annotation took its stamp between the two readings
        self.anchor, self.bracket_s = (a + b) / 2, (b - a) / 2
        self.active = True

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(HOST_PREFIX + name)

    def due(self) -> bool:
        return (self.active
                and time.perf_counter() - self.anchor >= self.seconds)

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        after = self._counters()       # before stop_trace, which takes seconds
        self.counters = {k: v - self.counters.get(k, 0)
                         for k, v in after.items()
                         if v != self.counters.get(k, 0)}
        self._window.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()
        self.done = True


# ------------------------------------------- what the program says of itself
def scopes_of(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction name: its `op_name` path, "" for none})
    of a compiled module's text: the name stack jax wrote, named scopes
    included."""
    m = re.match(r"HloModule (\S+?),", hlo_text)
    held = {}
    for line in hlo_text.splitlines():
        hit = INSTRUCTION.match(line)
        if hit:
            path = OP_NAME.search(line)
            held[hit.group(1)] = path.group(1) if path else ""
    return (m.group(1) if m else ""), held


def compiled_scopes(programs) -> dict:
    """{module: {instruction: path}} of the programs the window drove
    (`driver.programs()`: (name, jitted, arguments)), from their compiled
    text. This process compiled or loaded them in set-up: no second
    compile."""
    return dict(scopes_of(fn.lower(*args).compile().as_text())
                for _name, fn, args in programs)


def program_rows(spans, anchor: float, window_start_ns: int,
                 since: float) -> list:
    """The recorder's spans that ended after `since` (host clock), on the
    device's timebase: [name, start_ns, dur_ns, trace_id, meta]."""
    rows = []
    for s in spans:
        if s.end < since:
            continue
        meta = {k: v for k, v in s.meta.items()
                if isinstance(v, (str, int, float, bool))}
        rows.append([s.name,
                     window_start_ns + round((s.start - anchor) * 1e9),
                     round((s.end - s.start) * 1e9), s.trace_id, meta])
    return sorted(rows, key=lambda r: r[1])


def anchor_error_us(rows, annotations, lo: int, hi: int) -> list:
    """How far each host annotation inside the window lies from the
    recorder's row of the same name mapped through the anchor: the smaller
    of the distances at its start and at its end. The anchor's error moves
    both alike; a thread switch between the recorder's stamp and the
    annotation's moves one of them."""
    by_name: dict = {}
    for name, start, dur, _tid, _meta in rows:
        by_name.setdefault(name, []).append((start, start + dur))
    errs = []
    for name, start, dur in annotations:
        mine = by_name.get(name)
        if not mine or not lo <= start <= hi:
            continue
        i = bisect.bisect_left(mine, (start, 0))
        errs.append(min(min(abs(start - a), abs(start + dur - b))
                        for a, b in mine[max(i - 1, 0): i + 1]) / 1e3)
    return errs
