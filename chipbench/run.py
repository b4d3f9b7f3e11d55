#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. It finds the cell's configuration, traffic mix
and per-layer metrics, and the code they name, by name
(chipbench/manifest.py), makes weights and
data on the device from --seed, warms up this cell's shapes and no others
(all of that is `setup_s`), measures for --seconds, reads the device's
memory peak, and only then frees the program and runs the plain reference
that decides `correct`. The last line of stdout is the result object and
nothing else, and it is checked by chipbench/lastline.py before it is
printed: a run whose object fails prints the reason on stderr, no last
line, and exits 1. Without a TPU (or with fewer chips than the cell asks
for, or a device kind chipbench/peaks.json does not know): exit 2, nothing
on stdout.

With `--trace 1` the window is traced for a few seconds, and the trace is
read together with what the program says of itself (chipbench/trace.py: the
named scopes of its compiled programs, the recorder's spans on the device's
clock, its counters' deltas), so that every per-layer metric of the cell
comes off ONE trace through its own reducer (`manifest.find` finds it),
and `breakdown` names an operation `<scope>:<kind>` and an idle gap by the
program's span over it. A `[chipbench] plane {...}` line says how good the
clock anchor was and what the scopes cover.

`--rehearse-cpu` (never chosen automatically) runs the same control flow in
the sandbox at the tiny sizes of the files' `rehearse` blocks, kernels
interpreted, reading fixtures/<cell>.plane.json, or the driver kind's
fixtures/<kind>.plane.json, in place of a device trace; its last line is
stamped "platform": "cpu" and is checked for form only. It is no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()        # as near to process start as Python gets

import argparse
import faulthandler
import json
import os
import shutil
import statistics
import sys
import threading
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import lastline, manifest  # noqa: E402


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


class CompileCount:
    """Traces, lowerings and compiles jax makes, from jax.monitoring: the
    window must add none."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.n += 1


def device_or_exit(cell, rehearse: bool):
    import jax

    devs = jax.devices()
    dev = devs[0]
    peaks_all = manifest.load_json(manifest.HERE / "peaks.json")
    if rehearse:
        return dev, len(devs), peaks_all["TPU v5 lite"]
    if dev.platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: jax found {len(devs)} x {dev.platform!r} "
              f"({dev.device_kind}); {cell.name} needs {cell.chips} TPU "
              "chip(s)", file=sys.stderr)
        sys.exit(2)
    if dev.device_kind not in peaks_all:
        print(f"chipbench: device kind {dev.device_kind!r} is not in "
              "chipbench/peaks.json: an unknown chip is an error, not a "
              "default", file=sys.stderr)
        sys.exit(2)
    return dev, len(devs), peaks_all[dev.device_kind]


def read_trace(cell, driver, tracer, trace_dir: Path, since: float):
    """(trace, log, report) of the traced window: the device's planes, and
    on the same clock what the program said of itself while it ran."""
    from fedml_tpu.utils.events import recorder

    from chipbench import reduce
    from chipbench import trace as tr

    spans = list(recorder.spans)
    scopes = tr.compiled_scopes(driver.programs())
    trace = reduce.load_xplane(reduce.find_xplane(trace_dir), cell.chips,
                               annotations={s.name for s in spans})
    lo, hi = reduce.window_of(trace)
    trace["program"] = tr.program_rows(spans, tracer.anchor, lo, since)
    for c in trace["chips"]:
        c["scopes"] = scopes
    errs = tr.anchor_error_us(trace["program"], trace.pop("annotations"),
                              lo, hi)
    trace["anchor"] = {
        "bracket_us": tracer.bracket_s * 1e6, "matched": len(errs),
        "error_us_median": statistics.median(errs) if errs else None,
        "error_us_worst": max(errs) if errs else None}
    report = {"anchor": trace["anchor"],
              "spans_dropped": sum(recorder.dropped.values()),
              "scoped_instructions": {
                  k: f"{sum(bool(reduce.leaf(p)) for p in v.values())}/{len(v)}"
                  for k, v in scopes.items()}}
    return trace, {**driver.log, "counters": tracer.counters}, report


def reduce_trace(cell, trace: dict, specs: list, log: dict, peaks: dict,
                 states=()):
    from chipbench import reduce

    window_s, busy_s = reduce.window_seconds(trace), reduce.busy_seconds(trace)
    ctx = {"cell": cell, "log": log, "peaks": peaks,
           "window_s": window_s, "busy_s": busy_s}
    metrics = {}
    for m in specs:
        spec = cell.metric_file(m["name"])
        value = manifest.find("reducers", spec["reducer"])(spec, trace, ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, window_s, busy_s, reduce.breakdown(trace, states=states)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    traced, rehearse = bool(args.trace), args.rehearse_cpu
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    mf = manifest.load_manifest()
    cell = manifest.Cell(mf, args.workload)
    seconds = args.seconds if args.seconds is not None else mf["run_seconds"]
    seed = args.seed % 2 ** 32

    dev, n_dev, peaks = device_or_exit(cell, rehearse)
    from fedml_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    # jax keys a cached program WITHOUT its metadata, so a hit may hand back
    # the executable another tree compiled, carrying that tree's names (seen:
    # a round program without one `fed.*` scope). A traced run reads names
    # off its executables, so every run's keys include the metadata.
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    compiles = CompileCount()
    out_dir = manifest.HERE / "out" / cell.name
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    say(f"{cell.name} seed {seed} seconds {seconds} trace {int(traced)} on "
        f"{n_dev} x {dev.device_kind} ({dev.platform}); cache {cache_dir}"
        + ("  REHEARSAL: cpu, tiny sizes, no result" if rehearse else ""))

    from chipbench import compare
    from chipbench.trace import Tracer

    driver = manifest.find("drivers", cell.driver)(cell, seed, rehearse)
    driver.setup()
    setup_s = time.perf_counter() - _T0
    say(f"set-up {setup_s:.2f} s")

    trace_s = min(float(driver.traffic.get("trace_seconds", 4.0)), seconds)
    tracer = Tracer(trace_dir, trace_s, on=traced and not rehearse)
    since, c0 = time.perf_counter(), compiles.n
    res = driver.window(seconds, tracer)
    in_window = compiles.n - c0
    stats = dev.memory_stats() or {}
    # what the chip held at its fullest: the allocator's peak (arrays) and
    # what the runtime reserved beside it for the programs' temporaries,
    # which `peak_bytes_in_use` alone does not count (PERF.md section 6)
    peak = (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0))) or int(rehearse)
    say(f"memory_stats {json.dumps(stats)}")
    say(f"window: {res['attempted']} attempted, {res['failed']} failed, "
        f"{in_window} compile events inside it; {json.dumps(res['metrics'])}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": peak}
    obj = {"correct": False, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if traced:
        from chipbench import reduce

        t_read = time.perf_counter()
        if rehearse:
            kept = manifest.HERE / "fixtures" / f"{cell.name}.plane.json"
            trace = manifest.load_json(kept if kept.exists() else
                                       kept.with_name(
                                           f"{cell.driver}.plane.json"))
            log, report = trace["log"], {"anchor": trace.get("anchor", {})}
        else:
            trace, log, report = read_trace(cell, driver, tracer, trace_dir,
                                            since)
            with open(out_dir / "trace.trimmed.json", "w") as f:
                json.dump({**reduce.trim(trace), "log": log}, f)
            shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace read {time.perf_counter() - t_read:.2f} s")
        specs = manifest.metrics_for(mf, cell.name, traced=True)
        obj["metrics"], device["window_s"], device["busy_s"], \
            obj["breakdown"] = reduce_trace(cell, trace, specs, log, peaks,
                                            driver.states)
        report.update(coverage=reduce.coverage(trace))
        say("plane " + json.dumps(report))
    else:
        units = {m["name"]: m["unit"] for m in mf["end_to_end"]}
        values = {"setup_s": setup_s, **res["metrics"]}
        obj["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in values.items() if k in units}

    t_ref = time.perf_counter()
    numbers = driver.check()            # frees the program, runs the reference
    say(f"reference and comparison {time.perf_counter() - t_ref:.2f} s")
    obj["correct"], compared = compare.judge(numbers, driver.traffic["limits"])
    obj["compared"] = compared
    say("numbers read " + json.dumps(numbers))   # the unheld ones too

    why = lastline.problems(obj, mf, cell.name, traced, result=not rehearse)
    if in_window:
        why.append(f"{in_window} jax compile events inside the measured "
                   "window: every shape must be warmed in set-up")
    if why:
        say("the refused object: " + json.dumps(obj))
        print("chipbench: no result, the last line would be refused:\n  "
              + "\n  ".join(why), file=sys.stderr)
    sys.stdout.flush()
    for name, row in compared.items():     # the last lines of stderr
        print(f"compared {name} {row['value']:.6g} limit {row['limit']:.6g}",
              file=sys.stderr)
    if why:
        return 1
    print(json.dumps(obj), flush=True)
    return 0


if __name__ == "__main__":
    # a hang must not outlive the 1200 s a first run may take
    faulthandler.dump_traceback_later(1150, exit=True)
    sys.exit(main())
