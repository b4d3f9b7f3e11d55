"""The traced part of a window: `jax.profiler` around a few seconds of it,
with the harness's own spans written into the same trace."""
from __future__ import annotations

import contextlib
import time
from pathlib import Path

from chipbench.reduce import HOST_PREFIX, WINDOW_SPAN


class Tracer:
    """`on=False` is the untraced run: every method is then a no-op."""

    def __init__(self, out_dir: Path, seconds: float, on: bool):
        self.dir, self.seconds, self.on = Path(out_dir), seconds, on
        self.active = False
        self.done = False
        self._window = None
        self._t0 = 0.0

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

        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self._t0 = time.perf_counter()
        self.active = True

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(HOST_PREFIX + name)

    def due(self) -> bool:
        return self.active and time.perf_counter() - self._t0 >= self.seconds

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._window.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()
        self.done = True
