#!/usr/bin/env python3
"""The control of a cell's `correct`: the reference put in the program's
place, computed in the nearest precision below the one the configuration
states (fp8 for bfloat16), and the faults a cell can have planted in it.
Every one of them has to come out as NOT correct.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 [--rehearse-cpu]

Not part of a benchmark run. Run on the chip at the cell's own size to read
the upper ends the limits are set from (PERF.md has the readings), and kept
as a test at a size a test run can hold (tests/chipbench).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest  # noqa: E402


def read(workload: str, seed: int, rehearse: bool, cases=None) -> dict:
    """{case: numbers compared} for one seed: the control and each fault
    against the float32 reference of the same inputs."""
    cell = manifest.Cell(manifest.load_manifest(), workload)
    driver = manifest.find("drivers", cell.driver)(cell, seed, rehearse)
    return driver.controls(cases)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--cases", default=None,
                    help="comma-separated subset of the kind's cases")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from fedml_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    cases = args.cases.split(",") if args.cases else None
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = read(args.workload, seed, args.rehearse_cpu, cases)
        for case, numbers in rows.items():
            shown = {k: v for k, v in numbers.items() if not k.startswith("_")}
            print(f"control {args.workload} seed {seed} {case} "
                  + json.dumps(shown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
