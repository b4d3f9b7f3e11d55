"""From a profiler trace to per-layer metrics: the one reduction every PR uses.

A trace is reduced in two steps. `load_xplane` turns the profiler's
`.xplane.pb` into a plain dict (the form `fixtures/` keeps a trimmed copy of):

    {"chips": [{"programs": [[name, start_ns, dur_ns], ...],   # XLA Modules
                "ops":      [[name, start_ns, dur_ns], ...]}], # XLA Ops
     "host":  [[name, start_ns, dur_ns], ...]}   # the harness's annotations

and the reducers below read that dict. Every reducer returns None when it
finds nothing to read; it never returns 0 for a share.
"""
from __future__ import annotations

import re
import statistics
from pathlib import Path

WINDOW_SPAN = "chipbench.window"
HOST_PREFIX = "chipbench."
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WRAPPERS = ("while", "conditional", "call")   # they span their bodies' ops


# ------------------------------------------------------------------ loading
def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(text: str) -> str:
    """An operation's event is named by its whole HLO text ("%fusion.12 =
    bf16[...] fusion(...)"); a Mosaic kernel's instruction carries the
    kernel's own name ("%flash_fwd.16 = ..."). Keep the instruction's name:
    that is what the metric files' patterns match."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: Path, chips: int) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    by_id, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = {"programs": [], "ops": []}
            for line in plane.lines:
                if line.name == PROGRAM_LINE:
                    chip["programs"] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
                elif line.name == OP_LINE:
                    chip["ops"] = [
                        [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
            by_id[int(m.group(1))] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    used = [by_id[i] for i in sorted(by_id)
            if by_id[i]["ops"] or by_id[i]["programs"]][:chips]
    return {"chips": used, "host": sorted(host, key=lambda e: e[1])}


# ---------------------------------------------------------------- intervals
def window_of(trace: dict) -> tuple[int, int]:
    """The traced window: the harness's own span around the steady rounds."""
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, start, dur = spans[-1]
    return start, start + dur


def clipped(events, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def merged(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals) -> int:
    return sum(b - a for a, b in merged(intervals))


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device inside the window:
    the UNION of the operations' intervals, averaged over the chips used.
    A sum over overlapping lines could pass the window; a union cannot."""
    lo, hi = window_of(trace)
    per_chip = [union_ns(clipped(c["ops"] or c["programs"], lo, hi))
                for c in trace["chips"]]
    return sum(per_chip) / len(per_chip) / 1e9 if per_chip else 0.0


def window_seconds(trace: dict) -> float:
    lo, hi = window_of(trace)
    return (hi - lo) / 1e9


def _matching(events, patterns) -> list:
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in rx)]


def _in_window(events, lo, hi) -> list:
    """Events that lie wholly inside the window (whole executions only)."""
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


# ----------------------------------------------------------------- reducers
def program_device_ms(spec: dict, trace: dict, ctx: dict):
    """Device time of the matching programs: `per` says over what —
    "execution" (mean or median of the executions' durations) or a count
    from the harness's log (summed time / that count)."""
    lo, hi = window_of(trace)
    ev = _in_window(_matching(trace["chips"][0]["programs"],
                              spec["programs"]), lo, hi)
    if not ev:
        return None
    durs = [d / 1e6 for _, _, d in ev]
    per = spec.get("per", "execution")
    if per == "execution":
        return getattr(statistics, spec.get("stat", "mean"))(durs)
    n = ctx["log"].get(per)
    return sum(durs) / n if n else None


def program_gap_ms(spec: dict, trace: dict, ctx: dict):
    """Median idle time on the device between one matching program and the
    next: the gap between them less whatever other program ran in it."""
    lo, hi = window_of(trace)
    progs = trace["chips"][0]["programs"]
    ev = sorted(_in_window(_matching(progs, spec["programs"]), lo, hi),
                key=lambda e: e[1])
    if len(ev) < 2:
        return None
    gaps = []
    for (_, s0, d0), (_, s1, _) in zip(ev, ev[1:]):
        a, b = s0 + d0, s1
        if b <= a:
            gaps.append(0.0)
            continue
        gaps.append((b - a - union_ns(clipped(progs, a, b))) / 1e6)
    cap = spec.get("ignore_gaps_over_ms")
    if cap is not None:        # the engine with no slot active is not waiting
        gaps = [g for g in gaps if g <= cap] or gaps
    return statistics.median(gaps)


def kernel_roofline(spec: dict, trace: dict, ctx: dict):
    """Least time the chip could take for the kernels' work, over the time
    their events took. The work comes from `work.py`: per event at the
    call's shapes, or in total from the harness's own log of the traffic."""
    from chipbench import work

    lo, hi = window_of(trace)
    ev = _in_window(_matching(trace["chips"][0]["ops"], spec["kernels"]),
                    lo, hi)
    took = sum(d for _, _, d in ev) / 1e9
    if not ev or took <= 0:
        return None
    w = work.WORK[spec["work"]](ctx["cell"], ctx["log"])
    scale = len(ev) / spec["events_per_call"] if "events_per_call" in spec \
        else 1.0
    peaks = ctx["peaks"]
    least = scale * max(w["flops"] / peaks["bf16_flops_per_s"],
                        w["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took


def mfu(spec: dict, trace: dict, ctx: dict):
    """The whole step's share of the chip's peak: model FLOPs of the work
    done in the traced window over (`window_s` or `busy_s`) x peak."""
    from chipbench import work

    w = work.WORK[spec["work"]](ctx["cell"], ctx["log"])
    seconds = ctx[spec.get("over", "window_s")]
    if not w["flops"] or seconds <= 0:
        return None
    return 100.0 * w["flops"] / (seconds * ctx["peaks"]["bf16_flops_per_s"]
                                 * len(trace["chips"]))


REDUCERS = {"program_device_ms": program_device_ms,
            "program_gap_ms": program_gap_ms,
            "kernel_roofline": kernel_roofline, "mfu": mfu}


# ---------------------------------------------------------------- breakdown
def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    by what the harness was doing (its innermost span at the gap's middle)."""
    lo, hi = window_of(trace)
    chip = trace["chips"][0]
    ops: dict[str, int] = {}
    for name, start, dur in chip["ops"]:
        if start >= lo and start + dur <= hi:
            key = re.sub(r"[.\d]+$", "", name) or name
            if key not in WRAPPERS:
                ops[key] = ops.get(key, 0) + dur
    busy = merged(clipped(chip["ops"] or chip["programs"], lo, hi))
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    spans = [e for e in trace["host"] if e[0] != WINDOW_SPAN]
    gaps: dict[str, int] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        inner = [e for e in spans if e[1] <= mid < e[1] + e[2]]
        name = (min(inner, key=lambda e: e[2])[0][len(HOST_PREFIX):]
                if inner else "between_spans")
        gaps[name] = gaps.get(name, 0) + (b - a)
    fmt = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": fmt(ops), "idle_gaps": fmt(gaps)}


def trim(trace: dict, keep_ops: int = 300, per_kind: int = 6) -> dict:
    """A copy small enough to keep under fixtures/: of what lies inside the
    window, every program event, the harness's spans, the first `keep_ops`
    operations of each chip and, past those, the first `per_kind` of every
    kind of operation (so that each kernel a metric names is still there)."""
    lo, hi = window_of(trace)
    chips = []
    for c in trace["chips"]:
        inside = _in_window(c["ops"], lo, hi)
        ops, seen = inside[:keep_ops], {}
        for o in inside[keep_ops:]:
            kind = re.sub(r"[.\d]+$", "", o[0])
            if seen.setdefault(kind, 0) < per_kind:
                seen[kind] += 1
                ops.append(o)
        chips.append({"programs": _in_window(c["programs"], lo, hi),
                      "ops": ops})
    return {"chips": chips, "host": trace["host"]}
