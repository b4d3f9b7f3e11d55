#!/usr/bin/env python3
"""Find the highest rate a serving cell's engine sustains, once, on the chip.

    python3 chipbench/sweep.py --workload olmo1b_decode_chat --rates 4,6,8,10,12,14 --seconds 20

One process, one replica, one set-up; then a window per rate over the
cell's own mix. Per rate it prints the completed share, the requests still
in flight at the middle and at the close of the window, the window's
end-to-end metrics and the tokens served per second inside the window, and
at the end names the knee: the most tokens per second any rate was served
(the engine's capacity on this mix), over the mix's mean output length: the
request rate above which a backlog has to grow. Four fifths of the knee goes
into the cell's traffic file as a number, and this table into PERF.md. Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest  # noqa: E402


def in_flight(rows, t: float) -> int:
    return sum(1 for r in rows if r.plan.due <= t and not (
        r.ok and r.token_times[-1] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
    from fedml_tpu.utils import enable_compilation_cache

    from chipbench.trace import Tracer

    enable_compilation_cache()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    driver = manifest.find("drivers", cell.driver)(cell, args.seed,
                                                   args.rehearse_cpu)
    driver.setup()
    table = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        driver.traffic["rate_rps"] = rate
        driver.seed = args.seed + i
        res = driver.window(args.seconds, Tracer("", 0.0, on=False))
        rows = driver.rows
        row = {"rate_rps": rate, "due": res["attempted"],
               "completed_share": 1 - res["failed"] / res["attempted"],
               "in_flight_mid": in_flight(rows, args.seconds / 2),
               "in_flight_close": in_flight(rows, args.seconds),
               "tokens_per_s": sum(t <= args.seconds for r in rows
                                   for t in r.token_times) / args.seconds,
               **res["metrics"]}
        row["mean_output"] = sum(r.plan.max_new for r in rows) / len(rows)
        table.append(row)
        print("sweep " + json.dumps(row), flush=True)
    driver.free()
    capacity = max(r["tokens_per_s"] for r in table)
    knee = capacity / table[-1]["mean_output"]
    print("sweep knee " + json.dumps(
        {"capacity_tokens_per_s": capacity, "knee_rps": knee,
         "four_fifths": 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
