"""The reducers: from the trace's plain dict to ONE number of a per-layer metric.

A metric file names its kind by `reducer`, and `manifest.find("reducers",
kind)` finds it: `reducers/<kind>.py`'s function `<kind>`, or one of the
seven below (`__all__`). A reducer is `f(spec, trace, ctx)`: `spec` is the
metric's file, `trace` the dict chipbench/reduce.py describes (the device's
programs and operations, each chip's operation-to-scope map, the program's
spans on the device's clock) and `ctx` holds `cell`, `log` (the harness's log
of the traced window, the program's counter deltas under `counters`),
`peaks`, `window_s` and `busy_s`. A reader that finds nothing to read
returns None; it never returns 0 for a share.
"""
from __future__ import annotations

import re
import statistics

from chipbench import manifest, reduce
from chipbench.loadgen import percentile

__all__ = ["program_device_ms", "program_gap_ms", "kernel_roofline", "mfu",
           "scope_share", "span_stat", "counter_ratio"]


def _whole(events, patterns, trace) -> list:
    """The matching events that lie wholly inside the traced window."""
    return reduce.in_window(reduce.matching(events, patterns),
                            *reduce.window_of(trace))


# ------------------------------------------------- off the device's two lines
def program_device_ms(spec: dict, trace: dict, ctx: dict):
    """Device time of the matching programs: `per` says over what —
    "execution" (mean or median of the executions' durations) or a count
    from the harness's log (summed time / that count)."""
    ev = _whole(trace["chips"][0]["programs"], spec["programs"], trace)
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
    progs = trace["chips"][0]["programs"]
    ev = sorted(_whole(progs, spec["programs"], trace), key=lambda e: e[1])
    if len(ev) < 2:
        return None
    gaps = []
    for (_, s0, d0), (_, s1, _) in zip(ev, ev[1:]):
        a, b = s0 + d0, s1
        if b <= a:
            gaps.append(0.0)
            continue
        other = reduce.union_ns(reduce.clipped(progs, a, b))
        gaps.append((b - a - other) / 1e6)
    cap = spec.get("ignore_gaps_over_ms")
    if cap is not None:        # the engine with no slot active is not waiting
        gaps = [g for g in gaps if g <= cap] or gaps
    return statistics.median(gaps)


def kernel_roofline(spec: dict, trace: dict, ctx: dict):
    """Least time the chip could take for the kernels' work, over the time
    their events took. The work comes from the metric's work function: per
    event at the call's shapes, or in total from the harness's own log of
    the traffic."""
    ev = _whole(trace["chips"][0]["ops"], spec["kernels"], trace)
    took = sum(d for _, _, d in ev) / 1e9
    if not ev or took <= 0:
        return None
    w = manifest.find("work", spec["work"])(ctx["cell"], ctx["log"])
    scale = len(ev) / spec["events_per_call"] if "events_per_call" in spec \
        else 1.0
    peaks = ctx["peaks"]
    least = scale * max(w["flops"] / peaks["bf16_flops_per_s"],
                        w["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took


def mfu(spec: dict, trace: dict, ctx: dict):
    """The whole step's share of the chip's peak: model FLOPs of the work
    done in the traced window over (`window_s` or `busy_s`) x peak."""
    w = manifest.find("work", spec["work"])(ctx["cell"], ctx["log"])
    seconds = ctx[spec.get("over", "window_s")]
    if not w["flops"] or seconds <= 0:
        return None
    return 100.0 * w["flops"] / (seconds * ctx["peaks"]["bf16_flops_per_s"]
                                 * len(trace["chips"]))


# --------------------------------------- off what the program says of itself
def scope_share(spec: dict, trace: dict, ctx: dict):
    """Device time of the window's operations that the spec selects, as a
    % of the device time of ALL operations of the matching programs (`over:
    programs`) or of the window's busy time (`over: busy`). Selected: the
    innermost scope is one of `leaf`, or the path holds one of `holds`, or
    (`unscoped`) it holds no scope at all; `programs` keeps only operations
    inside matching program executions. Unions of intervals off one line
    of the trace, so a share cannot pass 100."""
    rx = [re.compile(p) for p in spec.get("programs", [])]
    leaves, holds = set(spec.get("leaf", [])), spec.get("holds", [])
    picked, every, named = [], [], False
    for (_n, start, dur), prog, path in reduce.scoped_ops(trace):
        if rx and not any(r.search(prog) for r in rx):
            continue
        named = named or bool(path)
        every.append((start, start + dur))
        lf = reduce.leaf(path)
        if lf in leaves or any(h in path for h in holds) \
                or (spec.get("unscoped") and not lf):
            picked.append((start, start + dur))
    if not named:
        return None         # this program carries no scope: nothing to read
    whole = reduce.union_ns(every) if spec.get("over", "busy") == "programs" \
        else ctx["busy_s"] * 1e9
    if whole <= 0:
        return None
    share = 100.0 * reduce.union_ns(picked) / whole
    if share > 100.0 + 1e-6:
        raise ValueError(f"scope share {share:.3f}% passes 100%: operations "
                         "counted outside the time they are divided by")
    return share


def grouped_ms(spec: dict, trace: dict) -> dict:
    """{group: summed milliseconds} of the spans the spec names: one group
    a span, or one a `group_by` value (`trace_id`: a request; a meta key
    such as `round`: a round), over the run or the traced window only. A
    group counts only with every one of the names in it."""
    names = set(spec["spans"])
    rows = [r for r in trace.get("program", []) if r[0] in names]
    if spec.get("within", "run") == "window":
        lo, hi = reduce.window_of(trace)
        rows = [r for r in rows if r[1] >= lo and r[1] + r[2] <= hi]
    by = spec.get("group_by")
    groups: dict = {}
    for i, (name, _start, dur, tid, meta) in enumerate(rows):
        key = i if by is None else tid if by == "trace_id" else meta.get(by)
        if key is not None:
            g = groups.setdefault(key, {})
            g[name] = g.get(name, 0.0) + dur / 1e6
    return {k: sum(g.values()) for k, g in groups.items()
            if by is None or set(g) == names}


def span_stat(spec: dict, trace: dict, ctx: dict):
    """`median` or `p95` (the harness's own percentile, as for
    the first-token times) over the groups of `grouped_ms`, in ms."""
    totals = list(grouped_ms(spec, trace).values())
    if not totals:
        return None
    return (statistics.median(totals) if spec.get("stat") == "median"
            else percentile(totals, 95))


def counter_ratio(spec: dict, trace: dict, ctx: dict):
    """Delta over the traced window of one program counter over the delta
    of another times a constant from the cell's files (`times`: a dotted
    path into the traffic file), as a %."""
    deltas = ctx["log"].get("counters", {})
    num, den = deltas.get(spec["counter"]), deltas.get(spec["over"])
    if num is None or not den:
        return None
    times = ctx["cell"].traffic
    for key in spec.get("times", "").split("."):
        if key:
            times = times[key]
    return 100.0 * num / (den * (times if spec.get("times") else 1))
