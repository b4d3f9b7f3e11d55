"""chip_smoke.py and the one compilation-cache switch (ISSUE 21).

chip_smoke.py is the proof that the system starts on the chip; what can be
pinned without one is that it can never pass for the wrong reason: on a CPU
it exits non-zero in seconds with nothing on stdout, and its only other
mode is an explicit dry run that stamps its output as one.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from fedml_tpu import utils

ROOT = Path(__file__).resolve().parents[1]


def _run(*args, timeout):
    # one CPU device, as in a sandbox: conftest's 8-device XLA_FLAGS would
    # send the dry run down the multi-chip branches
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=str(ROOT))


def test_chip_smoke_refuses_a_cpu():
    r = _run(timeout=60)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout == ""                      # no result line to misread
    assert "platform 'cpu'" in r.stderr        # names what it found


@pytest.mark.slow
def test_chip_smoke_dry_run_is_stamped_as_one():
    r = _run("--dry-run-cpu", timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the verdict with EXACTLY these keys (the driver's
    # contract); everything else rides the report line before it
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    tag = "[chip_smoke] report "
    assert lines[-2].startswith(tag)
    out = json.loads(lines[-2][len(tag):])
    assert out["ok"] and out["dry_run"] is True and out["platform"] == "cpu"
    assert set(out["phases"]) == {"round", "fedllm", "serve"}
    assert all(p["ok"] for p in out["phases"].values())


# ------------------------------------------------- the compilation cache
@pytest.fixture
def cache_config():
    """enable_compilation_cache() writes process-global jax config; put it
    back (the suite runs with the persistent cache off — conftest.py)."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_defaults_to_a_fixed_dir_in_the_checkout(monkeypatch,
                                                       cache_config):
    monkeypatch.delenv(utils.CACHE_ENV, raising=False)
    got = utils.enable_compilation_cache()
    assert got == str(ROOT / ".jax_cache") == jax.config.jax_compilation_cache_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # gitignored: nothing built from a run is ever committed
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_cache_dir_is_left_alone_when_the_env_var_is_set(monkeypatch,
                                                         cache_config,
                                                         tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and the
    program sets no directory in code — whatever the config holds stays."""
    jax.config.update("jax_compilation_cache_dir", "/placed/from/outside")
    monkeypatch.setenv(utils.CACHE_ENV, str(tmp_path))
    got = utils.enable_compilation_cache()
    assert got == "/placed/from/outside" == jax.config.jax_compilation_cache_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_no_other_code_path_sets_the_cache_dir():
    hits = [str(p.relative_to(ROOT))
            for p in [*ROOT.glob("*.py"), *ROOT.glob("fedml_tpu/**/*.py"),
                      *ROOT.glob("examples/*.py"), *ROOT.glob("scripts/*.py")]
            if "jax_compilation_cache_dir" in p.read_text()]
    assert hits == ["fedml_tpu/utils/__init__.py"], hits
