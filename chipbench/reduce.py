"""From a profiler trace to per-layer metrics: the one reduction every PR uses.

A trace is reduced in two steps. `load_xplane` turns the profiler's
`.xplane.pb` into a plain dict (the form `fixtures/` keeps a trimmed copy of):

    {"chips": [{"programs": [[name, start_ns, dur_ns], ...],   # XLA Modules
                "ops":      [[name, start_ns, dur_ns], ...],   # XLA Ops
                "scopes":   {program: {operation: path}}}],    # run.py adds
     "host":  [[name, start_ns, dur_ns], ...],   # the harness's annotations
     "program": [[name, start_ns, dur_ns, trace_id, meta], ...]}   # run.py adds

and the reducers (chipbench/reducers/, found by the name a metric file
gives) read that dict with the functions below. Every reducer returns None
when it finds nothing to read; it never returns 0 for a share.

What chipbench/run.py adds is what the PROGRAM says of itself, on the
device's timebase (chipbench/trace.py gathers it): `scopes`, each
operation's `op_name` path from the compiled module's text (an operation
belongs to the INNERMOST of the program's named scopes on its path, `leaf`),
and `program`, the recorder's host spans. A trace without them (a program
that names nothing) reads as before.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

WINDOW_SPAN = "chipbench.window"
HOST_PREFIX = "chipbench."
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WRAPPERS = ("while", "conditional", "call")   # they span their bodies' ops
# the program's layer names on an operation's path: `fed.local_sgd`,
# `lm.attn`, `decode.kv_write`; jax's own components carry no dot
SCOPE = re.compile(r"(?<![\w.])[a-z][a-z0-9_]*\.[a-z_]\w*")


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


def load_xplane(path: Path, chips: int, annotations=()) -> dict:
    """The profiler's file as the plain dict. `annotations` names host
    annotations to keep beside the harness's own (the program's spans are
    TraceAnnotations too: the clock anchor's error is measured on them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    by_id, host, named = {}, [], []
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
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
                    elif e.name in annotations:
                        named.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    used = [by_id[i] for i in sorted(by_id)
            if by_id[i]["ops"] or by_id[i]["programs"]][:chips]
    by_start = lambda rows: sorted(rows, key=lambda e: e[1])
    return {"chips": used, "host": by_start(host),
            "annotations": by_start(named)}


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


def matching(events, patterns) -> list:
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in rx)]


def in_window(events, lo, hi) -> list:
    """Events that lie wholly inside the window (whole executions only)."""
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


# ------------------------------------------------------------------- scopes
def leaf(path: str) -> str:
    """The innermost of the program's scopes on a path ("" for none): a
    scope may sit inside parentheses (`vmap(fed.local_sgd)`), so it is a
    substring of the path, never a whole component."""
    found = SCOPE.findall(path)
    return found[-1] if found else ""


def kind_of(name: str) -> str:
    return re.sub(r"[.\d]+$", "", name) or name


def scoped_ops(trace: dict, chip: int = 0):
    """(operation, its program's name, its scope path) for every operation
    that lies wholly inside the window and is no loop wrapper."""
    lo, hi = window_of(trace)
    c = trace["chips"][chip]
    progs = sorted((p[1], p[1] + p[2], p[0]) for p in c["programs"])
    starts = [p[0] for p in progs]
    scopes = c.get("scopes") or {}
    for op in c["ops"]:
        name, start, dur = op
        if start < lo or start + dur > hi or kind_of(name) in WRAPPERS:
            continue
        i = bisect.bisect_right(starts, start) - 1
        prog = progs[i][2] if i >= 0 and start < progs[i][1] else ""
        yield op, prog, scopes.get(prog.split("(")[0], {}).get(name, "")


# ---------------------------------------------------------------- breakdown
def breakdown(trace: dict, top: int = 10, states=()) -> dict:
    """The device operations that took most time, keyed `<innermost
    scope>:<kind>` where the trace gives the operation a scope, and the
    longest idle gaps by the innermost span over each among the harness's
    AND the program's. `states` names the spans that say what state a
    request is in, not what a thread is doing: they run through many gaps
    and name none."""
    lo, hi = window_of(trace)
    chip = trace["chips"][0]
    ops: dict[str, int] = {}
    for (name, _s, dur), _prog, path in scoped_ops(trace):
        lf = leaf(path)
        key = f"{lf}:{kind_of(name)}" if lf else kind_of(name)
        ops[key] = ops.get(key, 0) + dur
    busy = merged(clipped(chip["ops"] or chip["programs"], lo, hi))
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    spans = [[e[0][len(HOST_PREFIX):], e[1], e[2]]
             for e in trace["host"] if e[0] != WINDOW_SPAN]
    spans += [r[:3] for r in trace.get("program", []) if r[0] not in states]
    gaps: dict[str, int] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        # one gap can run through several spans (a round's fetch, the
        # harness, the next round's sample): cut it where a span starts or
        # ends and give each piece to the innermost span over it
        over = [e for e in spans if e[1] < b and e[1] + e[2] > a]
        cuts = sorted({a, b} | {t for e in over for t in (e[1], e[1] + e[2])
                                if a < t < b})
        for lo_, hi_ in zip(cuts, cuts[1:]):
            mid = (lo_ + hi_) // 2
            inner = [e for e in over if e[1] <= mid < e[1] + e[2]]
            name = (min(inner, key=lambda e: e[2])[0] if inner
                    else "between_spans")
            gaps[name] = gaps.get(name, 0) + (hi_ - lo_)
    fmt = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": fmt(ops), "idle_gaps": fmt(gaps)}


def coverage(trace: dict) -> dict:
    """Of the operations of the programs whose text was read: seconds by
    innermost scope, the largest kinds under no scope, and the seconds of
    operations that are no instruction of the compiled text."""
    by_leaf, bare, unmapped = {}, {}, 0.0
    known = trace["chips"][0].get("scopes") or {}
    for (name, _s, dur), prog, path in scoped_ops(trace):
        held = known.get(prog.split("(")[0])
        if held is None:
            continue
        if name not in held:
            unmapped += dur / 1e9
        lf = leaf(path)
        by_leaf[lf or "(none)"] = by_leaf.get(lf or "(none)", 0) + dur / 1e9
        if not lf:
            k = kind_of(name)
            bare[k] = bare.get(k, 0) + dur / 1e9
    top = lambda d, n: dict(sorted(d.items(), key=lambda kv: -kv[1])[:n])
    return {"by_scope_s": top(by_leaf, 16), "unscoped_kinds_s": top(bare, 6),
            "unmapped_s": unmapped}


def trim(trace: dict, keep_ops: int = 200, per_kind: int = 4) -> dict:
    """A copy small enough to keep under fixtures/: of what lies inside the
    window, every program event, the harness's spans, the first `keep_ops`
    operations of each chip and, past those, the first `per_kind` of every
    (innermost scope, kind, program, backward or not, recomputed or not),
    so that each kernel and scope a metric names is still there; the scope
    paths of what was kept; and the program's rows whole (the readers take
    a tail over every request of the run)."""
    lo, hi = window_of(trace)
    chips = []
    for c in trace["chips"]:
        ops, seen = [], {}
        for op, prog, path in scoped_ops({**trace, "chips": [c]}):
            if len(ops) >= keep_ops:
                key = (leaf(path), kind_of(op[0]), prog.split("(")[0],
                       "transpose(" in path, "rematted_computation" in path)
                if seen.get(key, 0) >= per_kind:
                    continue
                seen[key] = seen.get(key, 0) + 1
            ops.append(op)
        # the loop wrappers too: busy time is a union that holds them
        ops += [o for o in in_window(c["ops"], lo, hi)
                if kind_of(o[0]) in WRAPPERS][:per_kind]
        names = {o[0] for o in ops}
        chips.append({
            "programs": in_window(c["programs"], lo, hi),
            "ops": sorted(ops, key=lambda o: o[1]),
            "scopes": {prog: {n: p for n, p in held.items() if n in names}
                       for prog, held in (c.get("scopes") or {}).items()}})
    return {"chips": chips, "host": trace["host"],
            "program": trace.get("program", []),
            "anchor": trace.get("anchor", {})}
