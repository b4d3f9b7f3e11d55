"""Examples-as-smoke-suite: every shipped example runs end-to-end (the
reference's CI pattern — its examples tree doubles as the smoke suite,
SURVEY.md §4 / .github/workflows/smoke_test_*). Each example asserts its own
success internally and exits 0; these tests just execute them in a fresh
interpreter on the virtual CPU mesh."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES = Path(__file__).resolve().parents[1] / "examples"

_CASES = [
    ("quick_start_simulation.py", []),
    ("cross_silo_federation.py", []),
    ("cross_silo_federation.py", ["--secagg"]),
    ("hierarchical_cross_silo.py", []),
    ("fedllm_lora.py", []),
    ("fedllm_lora.py", ["--ring"]),
    ("fedllm_lora.py", ["--int8"]),
    ("serving_deploy.py", []),
    ("federated_segmentation.py", []),
    ("attack_vs_defense.py", []),
    ("federated_analytics.py", []),
    ("platform_api.py", []),
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "script,args", _CASES,
    ids=[f"{s}{'_' + a[0].lstrip('-') if a else ''}" for s, a in _CASES])
def test_example_runs(script, args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    # examples set no platform themselves (they run on real hardware for
    # users); the smoke suite pins the child to the CPU
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / script), *args],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(EXAMPLES.parent))
    assert proc.returncode == 0, (
        f"{script} {args} failed\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}")
