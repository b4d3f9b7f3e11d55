#!/usr/bin/env python3
"""The program's own span plane, read on the device's clock.

    python3 chipbench/spanplane.py --workload <name> --seed <n> --seconds <s>

The twelve metrics of BENCHMARK.json time a layer from outside. The program
also says what it is doing, in three ways, and this module reads all three
off ONE traced run of a cell, on the device's timebase:

- named scopes inside the device programs (`fed.*`, `lm.*`, `decode.*`):
  each operation of the device trace gets its scope path, kept once per
  distinct operation of a program as `chip["scopes"] = {program: {operation:
  path}}`; an operation belongs to the INNERMOST scope on its path. The
  v5e trace itself carries no path (an operation event has three timing
  stats and is named by HLO text without `metadata=`), so the paths come
  from the compiled module's text, mapped by instruction name;
- the recorder's host spans (`fedml_tpu.utils.events.recorder.spans`),
  snapshotted when the window ends and put on the device's timebase with
  one anchor (the `chipbench.window` annotation's start against
  `perf_counter` at `Tracer.open()`), as `trace["program"]`: rows of
  `[name, start_ns, dur_ns, trace_id, meta]`;
- the program's counters (`fedml_tpu.utils.metrics.snapshot()` at
  `Tracer.open()` and `Tracer.stop()`); the log gets the deltas.

Three reducer kinds read them (`scope_share`, `span_stat`,
`counter_ratio`: REDUCERS below, the metric files beside the twelve in
metrics/, the nine `per_layer` entries in spanplane.json), and `breakdown`
names an operation `<scope>:<kind>` and an idle gap by the program's span
over it. A reader that finds nothing to read (a program without the scope,
span or counter) returns None and never raises.

This file is NOT wired into BENCHMARK.json: chipbench/run.py looks reducers
up in reduce.REDUCERS and lastline.py refuses a line that lacks a manifest
metric, so wiring it in edits files a PR of another kind may not touch
(PERF.md section 7 says which lines). Until then this is the one command
that prints a cell's twelve metrics and its share of the nine from the same
trace; `--rehearse-cpu` drives the same flow on fixtures/*.plane.json.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import bisect
import json
import re
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest, reduce  # noqa: E402
from chipbench.loadgen import percentile  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402

SCOPE = re.compile(r"(?:fed|lm|decode)\.\w+")     # the program's layer names
# a request's states, not what a thread was doing: they span many idle gaps
FIVE = ("serving.http.in", "serving.engine.queue", "serving.engine.prefill",
        "serving.engine.first_fetch", "serving.http.out")
REQUEST_SPANS = ("serving.request",) + FIVE
PENDING = manifest.HERE / "spanplane.json"


def say(msg: str) -> None:
    print(f"[spanplane] {msg}", flush=True)


# ----------------------------------------------------------------- the run
class PlaneTracer(Tracer):
    """The harness's Tracer, which also keeps what the program says of
    itself around the traced window: `anchor` (`perf_counter` at `open()`,
    the host-clock reading that belongs to the window annotation's start)
    and the program's counters at `open()` and `stop()`."""

    def __init__(self, out_dir, seconds: float, on: bool):
        super().__init__(out_dir, seconds, on)
        self.anchor = self.bracket_s = 0.0
        self.counters: dict = {}

    @staticmethod
    def _counters() -> dict:
        from fedml_tpu.utils import metrics

        return dict(metrics.snapshot()["counters"])

    def open(self) -> None:
        before = self._counters() if self.on else {}
        a = time.perf_counter()
        super().open()
        b = time.perf_counter()
        # the annotation took its stamp between the two readings
        self.anchor, self.bracket_s = (a + b) / 2, (b - a) / 2
        self.counters = before

    def stop(self) -> None:
        if self.active:     # before stop_trace, which takes seconds
            after = self._counters()
            self.counters = {k: v - self.counters.get(k, 0)
                             for k, v in after.items()
                             if v != self.counters.get(k, 0)}
        super().stop()


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


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]*)"')
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


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


def compiled_scopes(driver) -> dict:
    """{module: {instruction: path}} of the programs the window drove, from
    their compiled text (this process compiled or loaded them in set-up:
    no second compile). The training kinds name theirs
    (`driver.programs()`); the engine's step program is taken off the
    replica."""
    import jax

    if hasattr(driver, "programs"):
        lowered = [fn.lower(*args) for _n, fn, args in driver.programs()]
    else:
        eng = driver.runner.predictor.engine
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (eng.params, eng.adapters, eng._carry))
        lowered = [eng._step_jit.lower(*shapes)]
    return dict(scopes_of(lo.compile().as_text()) for lo in lowered)


def host_annotations(path: Path, span_names: set) -> list:
    """The host plane's annotations that carry one of the recorder's span
    names (`recorder.span` opens a TraceAnnotation of the same name):
    what the anchor's error is measured on."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events if e.name in span_names]
    return sorted(out, key=lambda e: e[1])


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


# ----------------------------------------------------------------- reading
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
    lo, hi = reduce.window_of(trace)
    c = trace["chips"][chip]
    progs = sorted((p[1], p[1] + p[2], p[0]) for p in c["programs"])
    starts = [p[0] for p in progs]
    scopes = c.get("scopes") or {}
    for op in c["ops"]:
        name, start, dur = op
        if start < lo or start + dur > hi or kind_of(name) in reduce.WRAPPERS:
            continue
        i = bisect.bisect_right(starts, start) - 1
        prog = progs[i][2] if i >= 0 and start < progs[i][1] else ""
        yield op, prog, scopes.get(prog.split("(")[0], {}).get(name, "")


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
    for (_n, start, dur), prog, path in scoped_ops(trace):
        if rx and not any(r.search(prog) for r in rx):
            continue
        named = named or bool(path)
        every.append((start, start + dur))
        lf = leaf(path)
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
    `ttft_p95_ms`) over the groups of `grouped_ms`, in ms."""
    totals = list(grouped_ms(spec, trace).values())
    if not totals:
        return None
    return (statistics.median(totals) if spec.get("stat") == "median"
            else percentile(totals, 95))


def counter_ratio(spec: dict, trace: dict, ctx: dict):
    """Delta of one program counter over delta of another times a constant
    from the cell's files (`times`: a dotted path into the traffic file),
    as a %."""
    deltas = ctx["log"].get("counters", {})
    num, den = deltas.get(spec["counter"]), deltas.get(spec["over"])
    if num is None or not den:
        return None
    times = ctx["cell"].traffic
    for key in spec.get("times", "").split("."):
        if key:
            times = times[key]
    return 100.0 * num / (den * (times if spec.get("times") else 1))


REDUCERS = {"scope_share": scope_share, "span_stat": span_stat,
            "counter_ratio": counter_ratio}


def breakdown(trace: dict, top: int = 10) -> dict:
    """reduce.breakdown with the program's names: an operation is keyed
    `<innermost scope>:<kind>` where the trace gave it a scope, idle time
    by the innermost span over it among the harness's AND the program's
    spans (a request's states left out: they are no thread's doing)."""
    lo, hi = reduce.window_of(trace)
    chip = trace["chips"][0]
    ops: dict = {}
    for (name, _s, dur), _prog, path in scoped_ops(trace):
        lf = leaf(path)
        key = f"{lf}:{kind_of(name)}" if lf else kind_of(name)
        ops[key] = ops.get(key, 0) + dur
    busy = reduce.merged(reduce.clipped(chip["ops"] or chip["programs"],
                                        lo, hi))
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    spans = [[e[0][len(reduce.HOST_PREFIX):], e[1], e[2]]
             for e in trace["host"] if e[0] != reduce.WINDOW_SPAN]
    spans += [r[:3] for r in trace.get("program", [])
              if r[0] not in REQUEST_SPANS]
    gaps: dict = {}
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


def coverage(trace: dict, programs: list) -> dict:
    """Of the matching programs' operations: seconds by innermost scope,
    and the largest kinds under no scope."""
    rx = [re.compile(p) for p in programs]
    by_leaf, bare, unmapped = {}, {}, 0.0
    known = trace["chips"][0].get("scopes") or {}
    for (name, _s, dur), prog, path in scoped_ops(trace):
        if not any(r.search(prog) for r in rx):
            continue
        if name not in known.get(prog.split("(")[0], {}):
            unmapped += dur / 1e9   # not an instruction of the compiled text
        lf = leaf(path)
        by_leaf[lf or "(none)"] = by_leaf.get(lf or "(none)", 0) + dur / 1e9
        if not lf:
            k = kind_of(name)
            bare[k] = bare.get(k, 0) + dur / 1e9
    top = lambda d, n: dict(sorted(d.items(), key=lambda kv: -kv[1])[:n])
    return {"by_scope_s": top(by_leaf, 16), "unscoped_kinds_s": top(bare, 6),
            "unmapped_s": unmapped}


def requests(trace: dict, slowest: int = 10) -> dict:
    """What the replica's own time to first token is made of, from the five
    spans of each request: how many requests have all five under one trace
    id, their worst gap between consecutive spans, and the means of the
    parts over all of them and over the `slowest` by their sum."""
    by: dict = {}
    for name, start, dur, tid, _meta in trace.get("program", []):
        if name in FIVE:
            by.setdefault(tid, {})[name] = (start, dur)
    whole = [g for g in by.values() if set(g) == set(FIVE)]
    if not whole:
        return {}
    gap = max(abs(g[b][0] - (g[a][0] + g[a][1])) / 1e6
              for g in whole for a, b in zip(FIVE[1:3], FIVE[2:4]))
    total = lambda g: sum(d for _s, d in g.values()) / 1e6
    worst = sorted(whole, key=total)[-slowest:]
    mean = lambda gs: {n[len("serving."):]: statistics.fmean(
        g[n][1] / 1e6 for g in gs) for n in FIVE}
    return {"requests": len(whole), "of": len(by),
            "engine_spans_worst_gap_ms": gap,
            "replica_ttft_p95_ms": percentile([total(g) for g in whole], 95),
            "mean_ms": mean(whole), f"slowest_{slowest}_mean_ms": mean(worst)}


def trim(trace: dict, keep_ops: int = 200, per_kind: int = 4) -> dict:
    """A copy small enough for fixtures/: reduce.trim's, then per
    (innermost scope, kind, program, backward or not, recomputed or not)
    the first `per_kind` operations more, the scope paths of what was kept,
    and of the program's rows those inside the window and every request's
    and round's."""
    out = reduce.trim(trace, keep_ops, per_kind)
    lo, hi = reduce.window_of(trace)
    for c_out, c in zip(out["chips"], trace["chips"]):
        kept = {tuple(o) for o in c_out["ops"]}
        seen: dict = {}
        for op, prog, path in scoped_ops({**trace, "chips": [c]}):
            key = (leaf(path), kind_of(op[0]), prog.split("(")[0],
                   "transpose(" in path, "rematted_computation" in path)
            if tuple(op) not in kept and seen.setdefault(key, 0) < per_kind:
                seen[key] += 1
                c_out["ops"].append(op)
        c_out["ops"].sort(key=lambda o: o[1])
        names = {o[0] for o in c_out["ops"]}
        c_out["scopes"] = {
            prog: {n: p for n, p in held.items() if n in names}
            for prog, held in (c.get("scopes") or {}).items()}
    out["program"] = [r for r in trace.get("program", [])
                      if r[0] in REQUEST_SPANS or r[0].startswith("fed.round")
                      or (r[1] >= lo and r[1] + r[2] <= hi)]
    out["anchor"] = trace.get("anchor", {})
    return out


# -------------------------------------------------------------------- main
def pending_for(mf: dict, workload: str) -> list:
    """The nine `per_layer` entries that wait in spanplane.json, for the
    cells that report the metric each moves."""
    e2e = {m["name"]: m for m in mf["end_to_end"]}
    return [m for m in manifest.load_json(PENDING)["per_layer"]
            if manifest.applies(m, workload)
            and manifest.applies(e2e[m["moves"]], workload)]


def reduce_plane(cell, trace: dict, specs: list, log: dict,
                 peaks: dict) -> dict:
    ctx = {"cell": cell, "log": log, "peaks": peaks,
           "window_s": reduce.window_seconds(trace),
           "busy_s": reduce.busy_seconds(trace)}
    out = {}
    for m in specs:
        spec = cell.metric_file(m["name"])
        value = REDUCERS[spec["reducer"]](spec, trace, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    rehearse = args.rehearse_cpu
    if rehearse:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
    from chipbench import drivers, lastline, run

    mf = manifest.load_manifest()
    cell = manifest.Cell(mf, args.workload)
    seconds = args.seconds if args.seconds is not None else mf["run_seconds"]
    seed = args.seed % 2 ** 32
    dev, n_dev, peaks = run.device_or_exit(cell, rehearse)
    from fedml_tpu.utils import enable_compilation_cache
    from fedml_tpu.utils.events import recorder

    enable_compilation_cache()
    # jax keys a cached program WITHOUT its metadata, so a hit may hand back
    # the executable another commit compiled, carrying that commit's names
    # (seen: a round program without one `fed.*` scope). This process reads
    # names off its executables, so its keys include the metadata: the first
    # run of a commit compiles, later ones load.
    import jax

    jax.config.update(METADATA_IN_KEY, True)
    compiles = run.CompileCount()
    out_dir = manifest.HERE / "out" / cell.name
    trace_dir = out_dir / "plane"
    shutil.rmtree(trace_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    say(f"{cell.name} seed {seed} seconds {seconds} on {n_dev} x "
        f"{dev.device_kind}" + ("  REHEARSAL: cpu, no result" * rehearse))

    driver = drivers.load(cell.driver)(cell, seed, rehearse)
    driver.setup()
    say(f"set-up {time.perf_counter() - _T0:.2f} s")
    trace_s = min(float(driver.traffic.get("trace_seconds", 4.0)), seconds)
    tracer = PlaneTracer(trace_dir, trace_s, on=not rehearse)
    since, c0 = time.perf_counter(), compiles.n
    res = driver.window(seconds, tracer)
    in_window = compiles.n - c0
    say(f"window: {res['attempted']} attempted, {res['failed']} failed, "
        f"{in_window} compile events inside it; "
        f"{json.dumps(res['metrics'])}")
    spans = list(recorder.spans)
    scopes = {} if rehearse else compiled_scopes(driver)
    driver.free()

    report: dict = {}
    if rehearse:
        kept = manifest.HERE / "fixtures" / f"{cell.driver}.plane.json"
        trace = manifest.load_json(kept if kept.exists() else kept.with_name(
            f"{cell.driver}.trace.json"))
        log = trace["log"]
    else:
        xp = reduce.find_xplane(trace_dir)
        trace = reduce.load_xplane(xp, cell.chips)
        lo, hi = reduce.window_of(trace)
        trace["program"] = program_rows(spans, tracer.anchor, lo, since)
        for c in trace["chips"]:
            c["scopes"] = scopes
        errs = anchor_error_us(
            trace["program"],
            host_annotations(xp, {r[0] for r in trace["program"]}), lo, hi)
        trace["anchor"] = {
            "bracket_us": tracer.bracket_s * 1e6, "matched": len(errs),
            "error_us_median": statistics.median(errs) if errs else None,
            "error_us_worst": max(errs) if errs else None}
        log = {**driver.log, "counters": tracer.counters}
        report["scoped_instructions"] = {
            k: f"{sum(bool(SCOPE.search(p)) for p in v.values())}/{len(v)}"
            for k, v in scopes.items()}
        with open(out_dir / "plane.trimmed.json", "w") as f:
            json.dump({**trim(trace), "log": log}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)

    old, window_s, busy_s, _bd = run.reduce_trace(
        cell, trace, manifest.metrics_for(mf, cell.name, traced=True), log,
        peaks)
    want = pending_for(mf, cell.name)
    new = reduce_plane(cell, trace, want, log, peaks)
    report.update(anchor=trace.get("anchor", {}),
                  counters={k: v for k, v in log.get("counters", {}).items()
                            if k.startswith("serving.engine.")},
                  coverage=coverage(trace, ["^jit_round_body", "^jit__step_all"]),
                  requests=requests(trace))
    say("report " + json.dumps(report))
    obj = {"attempted": res["attempted"], "failed": res["failed"],
           "metrics": {**old, **new},
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": n_dev, "window_s": window_s, "busy_s": busy_s},
           "breakdown": breakdown(trace)}
    why = [f"metric {m['name']!r} is missing: its reader found nothing"
           for m in want if m["name"] not in new]
    why += [f"share {m['name']!r} = {new[m['name']]['value']} is outside "
            "(0, 100]" for m in want if m["name"] in new and m["unit"] == "%"
            and not 0 < new[m["name"]]["value"] <= 100]
    bd = obj["breakdown"]
    if not all(len(bd[k]) <= 10 and all(lastline._number(r[1]) for r in bd[k])
               for k in bd):
        why.append("breakdown is not at most 10 [name, seconds] pairs each")
    if in_window:
        why.append(f"{in_window} jax compile events inside the window")
    print(json.dumps(obj), flush=True)
    if why:
        print("spanplane: refused:\n  " + "\n  ".join(why), file=sys.stderr)
    return 1 if why else 0


if __name__ == "__main__":
    import faulthandler

    faulthandler.dump_traceback_later(1150, exit=True)
    sys.exit(main())
