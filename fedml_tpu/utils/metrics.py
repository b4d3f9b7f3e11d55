"""Process-wide counters, gauges, and fixed-bucket histograms.

The events recorder (utils/events.py) answers "what happened when" — spans
and metric rows, bounded ring buffers, sinks. This module answers "how much,
how fast" with O(1)-memory instruments cheap enough for transport hot paths:
every byte a transport moves, every serving request, every XLA compile is a
counter bump or a histogram observe, never a row.

Design constraints (ISSUE 2 tentpole):
- hot-path writes are lock-free: each instrument keeps per-thread shards
  (a thread's first write registers its shard under a lock, every later
  write touches only thread-local state under the GIL);
- the whole process snapshots as ONE dict (`snapshot()` — exposed as
  `mlops.metrics_snapshot()` and by the `python -m fedml_tpu report` CLI
  verb), merging shards at read time;
- histograms are fixed-bucket (bisect into precomputed edges), so
  percentiles are bucket upper bounds — honest approximations that cost
  one integer increment per observation.

No reference equivalent: the reference ships sys-perf rows and span events
(core/mlops/mlops_device_perfs.py) but no transport/serving instrument
layer; motivated by the "Understanding Communication Backends in Cross-Silo
FL" byte/latency accounting (PAPERS.md) and VERDICT's comm-perf-floor gap.
"""
from __future__ import annotations

import bisect
import contextlib
import threading
import time
from typing import Optional, Sequence

# latency buckets in seconds: 1µs .. 60s, ~1-2-5 per decade. Wide enough for
# an in-process queue put (µs) and a cross-silo model exchange (seconds).
LATENCY_BUCKETS_S = (
    1e-6, 2e-6, 5e-6, 1e-5, 2e-5, 5e-5, 1e-4, 2e-4, 5e-4,
    1e-3, 2e-3, 5e-3, 1e-2, 2e-2, 5e-2, 1e-1, 2e-1, 5e-1,
    1.0, 2.0, 5.0, 10.0, 30.0, 60.0,
)

# RTT buckets in MILLISECONDS: 10µs loopback ack .. 10s WAN timeout. The
# per-link `comm.link.<src>.<dst>.rtt_ms` histograms (ISSUE 18) observe
# milliseconds, so the seconds-scale LATENCY_BUCKETS_S would collapse every
# loopback ack into its bottom bucket.
RTT_BUCKETS_MS = (
    0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0,
    10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1000.0, 2000.0, 5000.0, 10000.0,
)


class Counter:
    """Monotonic counter. `inc` touches only the calling thread's shard —
    the shard list mutates under a lock exactly once per thread. Shards of
    DEAD threads fold into a base total and are dropped at every read
    (thread-per-request servers like ThreadingHTTPServer would otherwise
    grow one shard per request forever)."""

    __slots__ = ("name", "_shards", "_base", "_lock", "_tl")

    def __init__(self, name: str):
        self.name = name
        self._shards: list[tuple] = []     # (owning thread, [value])
        self._base = 0
        self._lock = threading.Lock()
        self._tl = threading.local()

    def inc(self, n: int = 1) -> None:
        box = getattr(self._tl, "box", None)
        if box is None:
            box = [0]
            self._tl.box = box
            with self._lock:
                self._shards.append((threading.current_thread(), box))
        box[0] += n

    def value(self) -> int:
        with self._lock:
            live = []
            for t, b in self._shards:
                if t.is_alive():
                    live.append((t, b))
                else:      # a dead thread's box never mutates again
                    self._base += b[0]
            self._shards = live
            return self._base + sum(b[0] for _, b in self._shards)


class AtomicCounter:
    """Lock-protected up/down counter for in-flight accounting (serving
    queue depth, gateway inflight). Unlike Counter (monotonic, per-thread
    shards merged at read) this is ONE value mutated under a lock.
    `gauge` binds a registry gauge that is updated INSIDE the same lock —
    publishing the post-update value outside it would let two finishing
    threads reorder their gauge writes and leave a phantom depth behind."""

    __slots__ = ("_value", "_lock", "_gauge")

    def __init__(self, initial: int = 0, gauge: Optional[str] = None):
        self._value = int(initial)
        self._lock = threading.Lock()
        self._gauge = gauge

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self._value += n
            if self._gauge is not None:
                registry.gauge(self._gauge).set(self._value)
            return self._value

    def dec(self, n: int = 1) -> int:
        return self.inc(-n)

    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-value-wins gauge (queue depth, cache size). Plain attribute
    assignment — atomic under the GIL, no shards needed."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value: float = 0.0

    def set(self, v: float) -> None:
        self._value = v

    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram. `observe` is one bisect + three adds on the
    calling thread's shard; percentiles come from merged bucket counts and
    report the bucket UPPER BOUND (capped at the observed max). Like
    Counter, dead threads' shards fold into a base shard at read time so
    thread-per-request servers stay O(live threads)."""

    __slots__ = ("name", "edges", "_shards", "_base", "_lock", "_tl")

    def __init__(self, name: str, buckets: Sequence[float] = LATENCY_BUCKETS_S):
        self.name = name
        self.edges = tuple(buckets)
        self._shards: list[tuple] = []    # (owning thread, box)
        # [bucket counts (+1 overflow), sum, count, max]
        self._base = [[0] * (len(self.edges) + 1), 0.0, 0, float("-inf")]
        self._lock = threading.Lock()
        self._tl = threading.local()

    def observe(self, v: float) -> None:
        box = getattr(self._tl, "box", None)
        if box is None:
            box = [[0] * (len(self.edges) + 1), 0.0, 0, float("-inf")]
            self._tl.box = box
            with self._lock:
                self._shards.append((threading.current_thread(), box))
        box[0][bisect.bisect_left(self.edges, v)] += 1
        box[1] += v
        box[2] += 1
        if v > box[3]:
            box[3] = v

    @staticmethod
    def _fold(into: list, box: list) -> None:
        for i, c in enumerate(box[0]):
            into[0][i] += c
        into[1] += box[1]
        into[2] += box[2]
        if box[3] > into[3]:
            into[3] = box[3]

    def _merged(self) -> tuple[list[int], float, int, float]:
        with self._lock:
            live = []
            for t, b in self._shards:
                if t.is_alive():
                    live.append((t, b))
                else:
                    self._fold(self._base, b)
            self._shards = live
            merged = [list(self._base[0]), self._base[1], self._base[2],
                      self._base[3]]
            shards = [b for _, b in self._shards]
        for box in shards:
            self._fold(merged, box)
        return merged[0], merged[1], merged[2], merged[3]

    def snapshot(self) -> dict:
        counts, total, n, mx = self._merged()
        out = {"count": n, "sum": round(total, 9),
               "max": round(mx, 9) if n else None,
               "edges": list(self.edges), "counts": counts}
        for q in (0.5, 0.99):
            out[f"p{int(q * 100)}"] = percentile_from_counts(
                self.edges, counts, q, observed_max=mx if n else None)
        return out


def percentile_from_counts(edges: Sequence[float], counts: Sequence[int],
                           q: float,
                           observed_max: Optional[float] = None
                           ) -> Optional[float]:
    """Approximate q-quantile from bucket counts: the upper bound of the
    bucket holding the q-th observation (overflow bucket reports the
    observed max when known, else the last edge). Works on COUNT DELTAS
    too — comm_bench diffs two snapshots' counts to get a per-run p50/p99
    from the cumulative process-wide histogram."""
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target:
            if i < len(edges):
                return edges[i]
            return observed_max if observed_max is not None else edges[-1]
    return observed_max if observed_max is not None else edges[-1]


def percentile_from_snapshots(before: dict, after: dict, key: str,
                              q: float) -> Optional[float]:
    """q-quantile of ONE histogram over a measurement window: bucket-count
    deltas between two cumulative `snapshot()` dicts. The shared helper for
    every 'diff two snapshots' bench site (comm_bench's per-backend
    columns, bench.py's codec rows) — the windowing math lives once, next
    to percentile_from_counts."""
    ha = (after.get("histograms") or {}).get(key)
    if not ha:
        return None
    hb = (before.get("histograms") or {}).get(key)
    counts = [a - (hb["counts"][i] if hb else 0)
              for i, a in enumerate(ha["counts"])]
    return percentile_from_counts(ha["edges"], counts, q,
                                  observed_max=ha.get("max"))


class MetricsRegistry:
    """Name -> instrument map; instruments are created once and cached, so
    module-level `inc(name)` costs a dict get after the first call."""

    def __init__(self):
        self._instruments: dict = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls, *args):
        inst = self._instruments.get(name)
        if inst is None:
            with self._lock:
                inst = self._instruments.get(name)
                if inst is None:
                    inst = cls(name, *args)
                    self._instruments[name] = inst
        if not isinstance(inst, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(inst).__name__}, requested {cls.__name__}")
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  buckets: Sequence[float] = LATENCY_BUCKETS_S) -> Histogram:
        return self._get(name, Histogram, buckets)

    def snapshot(self) -> dict:
        """The whole process's instruments as one dict:
        {"counters": {name: int}, "gauges": {name: float},
         "histograms": {name: {count, sum, max, p50, p99, edges, counts}}}."""
        with self._lock:
            items = list(self._instruments.items())
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, inst in items:
            if isinstance(inst, Counter):
                out["counters"][name] = inst.value()
            elif isinstance(inst, Gauge):
                out["gauges"][name] = inst.value()
            else:
                out["histograms"][name] = inst.snapshot()
        return out

    def reset(self) -> None:
        """Drop every instrument (tests). In-flight writers holding a stale
        instrument keep writing into it harmlessly; new lookups start clean."""
        with self._lock:
            self._instruments = {}


registry = MetricsRegistry()


# ----------------------------------------------------- module conveniences
def counter(name: str) -> Counter:
    return registry.counter(name)


def gauge(name: str) -> Gauge:
    return registry.gauge(name)


def histogram(name: str,
              buckets: Sequence[float] = LATENCY_BUCKETS_S) -> Histogram:
    return registry.histogram(name, buckets)


def inc(name: str, n: int = 1) -> None:
    registry.counter(name).inc(n)


def set_gauge(name: str, v: float) -> None:
    registry.gauge(name).set(v)


def observe(name: str, v: float) -> None:
    registry.histogram(name).observe(v)


def snapshot() -> dict:
    return registry.snapshot()


def reset() -> None:
    registry.reset()


@contextlib.contextmanager
def timer(name: str):
    """Time a block into histogram `name` (seconds)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        registry.histogram(name).observe(time.perf_counter() - t0)


# ------------------------------------------------------ XLA compile tracking
class _TrackedJit:
    """Transparent wrapper over a jitted callable that turns PR 1's one-off
    retrace guard into an always-on metric: after every call it reads the
    function's compile-cache size into gauge `xla.compiles.<name>` and
    counts growth beyond the first entry as counter `xla.retraces.<name>`
    (a warm steady state is exactly one cache entry; every extra entry is a
    shape/dtype/weak-type retrace paying a fresh XLA compile).

    Each call also bumps the `xla.program.calls.<name>` counter, and a
    cache growth hands the call's abstract signature to the XLA ledger
    (utils/xla_ledger.py) so the freshly compiled program's
    cost_analysis/memory_analysis land as `xla.program.*` gauges — capture
    happens at compile events only, never on the steady-state path.
    Attribute access (lower, _cache_size, ...) passes through."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._seen = 0

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        size = self._fn._cache_size()
        from . import xla_ledger

        xla_ledger.note_call(self._name)
        if size > self._seen:
            if self._seen >= 1:
                registry.counter(
                    f"xla.retraces.{self._name}").inc(size - self._seen)
            self._seen = size
            registry.gauge(f"xla.compiles.{self._name}").set(size)
            xla_ledger.capture(self._name, self._fn, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def track_jit(fn, name: str):
    """Wrap a jitted entry point with compile/retrace accounting (see
    `_TrackedJit`)."""
    return _TrackedJit(fn, name)
