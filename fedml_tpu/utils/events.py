"""Span-event tracing + metrics sink.

Keeps the reference's span-event API shape — named phases wrapped in
started/ended pairs (reference: core/mlops/mlops_profiler_event.py:74-121,
used as mlops.event("train"/"agg"/"comm_c2s", event_started=...) at
simulation/sp/fedavg/fedavg_api.py:98-109) — but local-first: events go to an
in-process recorder and optionally to `jax.profiler` trace annotations, not to
an MQTT cloud. Sinks are pluggable for wandb/file export.

Beyond the reference (ISSUE 2):
- every span carries a trace context (trace_id / span_id / parent_id),
  thread-inherited and adoptable from a Message's headers, so a cross-silo
  send→receive→handle chain stitches into ONE trace;
- `export_chrome_trace` writes the Chrome trace-event JSON schema
  (chrome://tracing / ui.perfetto.dev) with comm/serving/round spans on
  separate named tracks;
- spans/metrics live in bounded ring buffers (default 100k rows,
  FEDML_TPU_EVENTS_CAP overrides) so week-long runs don't grow without
  bound; `summary()` keeps EXACT counts in an aggregate dict that survives
  ring eviction;
- eviction is NOT silent (ISSUE 17): every span pushed out past the cap is
  counted per track (`events.dropped.<track>` + `events.dropped_total`
  counters, mirrored in `recorder.dropped`), and `export_chrome_trace`
  warns loudly — a trace that quietly lost its oldest 30k spans reads as
  a short run, not a truncated one. Sinks see every row regardless (the
  JSONL file is unbounded; only the in-memory rings and the Chrome trace
  exported from them are capped).
"""
from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

logger = logging.getLogger("fedml_tpu")

DEFAULT_EVENTS_CAP = 100_000


def _events_cap() -> int:
    """Resolve the ring-buffer cap at RECORDER CONSTRUCTION, not import:
    `FEDML_TPU_EVENTS_CAP` set after this module is imported (tests,
    notebooks) must still take effect on the next EventRecorder()."""
    raw = os.environ.get("FEDML_TPU_EVENTS_CAP")
    if raw is None:
        return DEFAULT_EVENTS_CAP
    try:
        cap = int(raw)
        if cap < 1:
            raise ValueError(cap)
        return cap
    except ValueError:
        logger.warning("ignoring FEDML_TPU_EVENTS_CAP=%r (not a positive "
                       "integer); using %d", raw, DEFAULT_EVENTS_CAP)
        return DEFAULT_EVENTS_CAP

# jax.profiler's TraceAnnotation is resolved ONCE and cached (the hot path
# used to try/except-import it inside every span() call). Resolution is
# deferred to the first span so importing this module never drags jax in —
# the package's no-jax-at-import laziness (fedml_tpu/__init__.py).
_trace_annotation: Optional[Callable] = None


def _resolve_trace_annotation() -> Callable:
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except Exception:  # pragma: no cover — no jax in this process
            _trace_annotation = contextlib.nullcontext
    return _trace_annotation


# 64-bit span and trace ids from a generator of the recorder's own, seeded
# from os.urandom (and again in a forked child), not os.urandom per id: its
# getrandom call releases the GIL, and a span opened on a busy thread then
# waits behind every other runnable one (a serving process's engine thread
# behind the HTTP threads that stream its tokens)
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)


def _new_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


# ------------------------------------------------------------ trace context
# Thread-local (trace_id, span_id): spans inherit it, comm transports stamp
# it into Message headers, and receivers adopt it around handler dispatch.
_tl = threading.local()


def current_trace() -> tuple[Optional[str], Optional[str]]:
    """(trace_id, span_id) of the innermost open span on this thread, or
    (None, None) outside any span."""
    return getattr(_tl, "trace_id", None), getattr(_tl, "span_id", None)


@contextlib.contextmanager
def trace_context(trace_id: Optional[str], span_id: Optional[str] = None):
    """Adopt a propagated trace (e.g. a received Message's headers) for the
    current thread: spans opened inside stitch to `trace_id` with `span_id`
    as their parent. No-op when trace_id is falsy."""
    if not trace_id:
        yield
        return
    prev = (getattr(_tl, "trace_id", None), getattr(_tl, "span_id", None))
    _tl.trace_id, _tl.span_id = trace_id, span_id
    try:
        yield
    finally:
        _tl.trace_id, _tl.span_id = prev


class _Ring(deque):
    """Bounded deque that still supports the list-style slicing existing
    callers/tests use (`recorder.metrics[n0:]`)."""

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(itertools.islice(self, *i.indices(len(self))))
        return deque.__getitem__(self, i)


@dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    meta: dict = field(default_factory=dict)
    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""

    @property
    def duration(self) -> float:
        return self.end - self.start


class EventRecorder:
    """Process-wide event/metric recorder (cheap; always on).

    max_rows bounds BOTH ring buffers (spans and metric rows); the per-name
    aggregate behind `summary()` stays exact regardless of eviction.
    """

    def __init__(self, max_rows: Optional[int] = None):
        if max_rows is None:
            max_rows = _events_cap()
        self.spans: _Ring = _Ring(maxlen=max_rows)
        self.metrics: _Ring = _Ring(maxlen=max_rows)
        self.sinks: list[Callable[[str, dict], None]] = []
        # spans evicted past the cap, by Chrome-trace track, plus evicted
        # metric rows — the trace-truncation ledger (`summary()` stays
        # exact regardless; this says how much of the RING is gone)
        self.dropped: dict[str, int] = {t: 0 for t in self._TRACKS}
        self.dropped_rows = 0
        self._agg: dict[str, dict] = {}
        # guards the agg dict AND buffer append/snapshot pairs: deque
        # iteration raises RuntimeError if another thread appends mid-walk,
        # which would intermittently kill dump()/export_chrome_trace()
        # while comm/serving threads are still recording
        self._agg_lock = threading.Lock()
        # perf_counter -> wall-clock offset: spans time with perf_counter
        # (monotonic); dump/export add this so rows are orderable in wall
        # time across processes
        self._epoch = time.time() - time.perf_counter()

    # span_id/parent bookkeeping shared by span() and record_span()
    def _open_trace(self) -> tuple[str, str, str, bool]:
        parent = getattr(_tl, "span_id", None) or ""
        trace_id = getattr(_tl, "trace_id", None)
        fresh = trace_id is None
        if fresh:
            trace_id = _new_id()
        return trace_id, _new_id(), parent, fresh

    def _record(self, s: Span) -> None:
        with self._agg_lock:
            if self.spans.maxlen is not None \
                    and len(self.spans) == self.spans.maxlen:
                track = self._track_of(self.spans[0].name)
                self.dropped[track] += 1
                dropped = True
            else:
                dropped = False
            self.spans.append(s)
            agg = self._agg.setdefault(s.name, {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += s.duration
        if dropped:
            # outside the agg lock: the metrics registry has its own
            # locking and must not nest under ours
            from . import metrics as _mx

            _mx.inc(f"events.dropped.{track}")
            _mx.inc("events.dropped_total")

    def _sink_payload(self, s: Span) -> dict:
        # "t" (wall-clock start) makes sink rows orderable and lets the
        # attribution plane (utils/attribution.py) rebuild the timeline
        # from a finished run's events JSONL
        out = {"name": s.name, "duration": s.duration,
               "t": round(self._epoch + s.start, 6),
               "trace_id": s.trace_id, "span_id": s.span_id}
        if s.parent_id:
            out["parent_id"] = s.parent_id
        out.update(s.meta)
        return out

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        ctx = _resolve_trace_annotation()(name)
        trace_id, span_id, parent, fresh = self._open_trace()
        s = Span(name, time.perf_counter(), meta=meta,
                 trace_id=trace_id, span_id=span_id, parent_id=parent)
        _tl.trace_id, _tl.span_id = trace_id, span_id
        try:
            with ctx:
                yield s
        finally:
            s.end = time.perf_counter()
            _tl.span_id = parent or None
            if fresh:
                _tl.trace_id = None
            self._record(s)
            self._to_sinks(s)

    def _to_sinks(self, s: Span) -> None:
        if self.sinks:
            payload = self._sink_payload(s)
            for sink in self.sinks:
                sink("span", payload)

    def _closed(self, name: str, start: float, end: float,
                trace_id: Optional[str], parent_id: Optional[str],
                meta: dict) -> Span:
        """Ids, ring and aggregate of a span whose two ends are given."""
        if trace_id is None:
            trace_id, span_id, parent, _fresh = self._open_trace()
        else:
            span_id, parent = _new_id(), parent_id or ""
        s = Span(name, start, end, meta=meta, trace_id=trace_id,
                 span_id=span_id, parent_id=parent)
        self._record(s)
        return s

    def record_span(self, name: str, start: float, end: float,
                    trace_id: Optional[str] = None,
                    parent_id: Optional[str] = None, **meta) -> Span:
        """Record an interval that was not a `with` block on one thread: it
        crossed threads or engine iterations, and the caller timed both
        ends itself with `perf_counter` (the clock `span` uses). With
        `trace_id` the span joins that trace under `parent_id` (a request's
        life inside the engine hangs under its `serving.request`); without,
        it inherits the calling thread's open span like `span` does. Ring,
        aggregate and sinks see it exactly as they see a `with` span."""
        s = self._closed(name, start, end, trace_id, parent_id, meta)
        self._to_sinks(s)
        return s

    def log_block_span(self, name: str, rounds, duration: float, **meta):
        """Record a span over a round BLOCK (round-block execution runs K
        rounds as one async-dispatched XLA program, so the caller measures
        dispatch→materialization itself and reports it here): ONE span
        tagged with the covered round range, plus one sink row PER ROUND
        with the amortized duration — per-round dashboards keep their
        cadence when the engine stops paying per-round dispatches. Rows are
        flagged `block: true` because the amortized figure divides the
        block's wall clock evenly, and under a pipeline depth > 1 adjacent
        block spans overlap (block i+1 is in flight while block i drains),
        so summing them can exceed wall time."""
        rounds = list(rounds)
        end = time.perf_counter()
        # record_span's ring and aggregate; the sink rows are per ROUND
        s = self._closed(name, end - duration, end, None, None,
                         {"rounds": [rounds[0], rounds[-1]], **meta}
                         if rounds else dict(meta))
        per_round = duration / max(len(rounds), 1)
        for sink in self.sinks:
            for r in rounds:
                sink("span", {"name": name, "duration": per_round,
                              "round": r, "block": True,
                              "trace_id": s.trace_id, "span_id": s.span_id,
                              **meta})

    def log(self, metrics: dict):
        with self._agg_lock:
            dropped = (self.metrics.maxlen is not None
                       and len(self.metrics) == self.metrics.maxlen)
            if dropped:
                self.dropped_rows += 1
            self.metrics.append(metrics)
        if dropped:
            from . import metrics as _mx

            _mx.inc("events.dropped_total")
        for sink in self.sinks:
            sink("metrics", metrics)

    def summary(self) -> dict:
        """Per-span-name {count, total_s}. Exact even after ring eviction:
        the aggregate is updated at record time, never recomputed from the
        bounded buffer."""
        with self._agg_lock:
            return {k: dict(v) for k, v in self._agg.items()}

    def dump(self, path: str):
        with self._agg_lock:       # stable snapshot vs concurrent appends
            spans, metrics = list(self.spans), list(self.metrics)
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps({
                    "span": s.name, "dur": s.duration,
                    # wall-clock + monotonic start make dumped traces
                    # orderable (and mergeable across dumps)
                    "t": round(self._epoch + s.start, 6),
                    "start": round(s.start, 9),
                    "trace_id": s.trace_id, **s.meta}) + "\n")
            for m in metrics:
                f.write(json.dumps({"metrics": m}) + "\n")

    # --------------------------------------------------- Chrome trace export
    _TRACKS = ("round", "comm", "serving", "other")

    @staticmethod
    def _track_of(name: str) -> str:
        if name.startswith(("comm.", "comm_")) or name == "comm":
            return "comm"
        if name.startswith("serving"):
            return "serving"
        if name.startswith(("train", "eval", "round", "block", "agg",
                            "local_", "fit", "fed.round")):
            return "round"
        return "other"

    def export_chrome_trace(self, path: str) -> str:
        """Write every recorded span in the Chrome trace-event JSON schema
        (`{"traceEvents": [...]}` of complete "X" events) — loadable in
        chrome://tracing and ui.perfetto.dev. Tracks: comm, serving, and
        round spans land on separately named threads of one process (via
        "M" thread_name metadata events); `args` carries each span's meta
        plus its trace_id/span_id/parent_id so a stitched cross-silo trace
        is searchable by id.

        A trace exported after ring eviction is TRUNCATED — the oldest
        spans are gone. That is surfaced loudly: a warning log with the
        per-track drop counts, and the same counts in the process metadata
        event's args (visible in the Perfetto process details)."""
        dropped = {t: n for t, n in self.dropped.items() if n}
        if dropped:
            logger.warning(
                "chrome trace is TRUNCATED: %d spans were dropped past the "
                "ring cap (%s) before this export — the oldest part of the "
                "run is missing; raise FEDML_TPU_EVENTS_CAP to keep more",
                sum(dropped.values()),
                ", ".join(f"{t}: {n}" for t, n in sorted(dropped.items())))
        tids = {t: i for i, t in enumerate(self._TRACKS)}
        meta_args: dict = {"name": "fedml_tpu"}
        if dropped:
            meta_args["dropped_spans"] = dict(sorted(dropped.items()))
        events: list[dict] = [{"ph": "M", "pid": 0, "tid": 0,
                               "name": "process_name",
                               "args": meta_args}]
        for t, i in tids.items():
            events.append({"ph": "M", "pid": 0, "tid": i,
                           "name": "thread_name", "args": {"name": t}})
        with self._agg_lock:       # stable snapshot vs concurrent appends
            spans = list(self.spans)
        for s in spans:
            end = s.end if s.end else s.start
            cat = self._track_of(s.name)
            args = {k: v for k, v in s.meta.items()
                    if isinstance(v, (str, int, float, bool))}
            args["trace_id"] = s.trace_id
            args["span_id"] = s.span_id
            if s.parent_id:
                args["parent_id"] = s.parent_id
            events.append({
                "name": s.name, "cat": cat, "ph": "X", "pid": 0,
                "tid": tids[cat],
                "ts": round((self._epoch + s.start) * 1e6, 3),
                "dur": round(max(end - s.start, 0.0) * 1e6, 3),
                "args": args,
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path


recorder = EventRecorder()
