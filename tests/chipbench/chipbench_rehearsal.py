"""Shared by the three test_chipbench_correct_* files: one rehearsal run of
the harness in this process (chipbench/run.py --rehearse-cpu skips the look
for a chip and drives the rest of a run at tiny sizes on the CPU)."""
import json

from chipbench import run


def rehearse(capsys, workload: str, seed: int = 3, seconds: float = 0.5,
             trace: int = 0):
    """(exit code, last line as an object) of one rehearsal run."""
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse-cpu"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return rc, json.loads(lines[-1])
