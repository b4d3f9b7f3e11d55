"""The benchmark takes a configuration it was not written around as new files
and appended manifest entries ALONE. `stranger/` holds what a `model_config`
PR would bring for a `model_type` no file of chipbench/ mentions: a model
builder (a block with fewer KV heads than heads, which the program's own
block cannot express), a plain reference, a work function, a metric that
reads a scope of the model's own through `scope_share` and one that reads
counters of its own through `counter_ratio`, a traffic file of the `fedlora`
kind, the rehearsal's trace of the cell. The test lays them over a copy of
chipbench/ and BENCHMARK.json (what the driver does with such a PR's files),
runs `run.py --rehearse-cpu` there untraced and traced, and checks the last
lines; no file that was there differs from the tree's."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import lastline, manifest

HERE = Path(__file__).resolve().parent
ADDED = HERE / "stranger"
CELL = "squid_tiny_fedlora_s2"
SKIP = shutil.ignore_patterns("__pycache__", "out", ".pytest_cache",
                              "stranger")


def digests(root: Path) -> dict:
    return {f.relative_to(root).as_posix():
            hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(root.rglob("*")) if f.is_file()}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("benchmark_copy")
    shutil.copytree(manifest.HERE, root / "chipbench", ignore=SKIP)
    shutil.copytree(HERE, root / "tests" / "chipbench", ignore=SKIP)
    shutil.copy(manifest.ROOT / "BENCHMARK.json", root)
    before = digests(root)
    for f in sorted((ADDED / "chipbench").rglob("*")):
        if f.is_file():
            to = root / f.relative_to(ADDED)
            assert not to.exists(), f"{to} is there already: that is an edit"
            to.parent.mkdir(exist_ok=True)
            shutil.copy(f, to)
    more = json.loads((ADDED / "manifest_entries.json").read_text())
    mf = json.loads((root / "BENCHMARK.json").read_text())
    for group in ("configs", "workloads", "per_layer"):
        mf[group] += more[group]
    for m in mf["end_to_end"]:
        m.get("workloads", []).extend(more["reports"].get(m["name"], []))
    (root / "BENCHMARK.json").write_text(json.dumps(mf, indent=1))
    return root, before, mf


def in_copy(root: Path, *argv) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{root}{os.pathsep}{manifest.ROOT}",
           "JAX_COMPILATION_CACHE_DIR": str(root / ".jax_cache")}
    return subprocess.run([sys.executable, *argv], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def rehearse(root: Path, mf: dict, traced: int) -> dict:
    done = in_copy(root, "chipbench/run.py", "--workload", CELL, "--seed",
                   "2147483659", "--seconds", "1.0", "--trace", str(traced),
                   "--rehearse-cpu")
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    obj = json.loads(done.stdout.strip().splitlines()[-1])
    assert lastline.problems(obj, mf, CELL, bool(traced), result=False) == []
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 3 and list(obj)[-1] == "compared"
    assert set(obj["compared"]) == {"grad1_gap", "change_gap"}
    return obj


def test_no_file_of_the_harness_mentions_the_strangers_model_type():
    kind = json.loads((ADDED / "chipbench/configs/squid_tiny.json")
                      .read_text())["model"]["model_type"]
    for f in manifest.HERE.rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts \
                and "out" not in f.parts:
            assert kind not in f.read_text(errors="ignore"), f
    assert kind not in (manifest.ROOT / "BENCHMARK.json").read_text()
    with pytest.raises(KeyError, match=r"chipbench/models/squid\.py"):
        manifest.find("models", kind)


def test_no_file_that_was_there_differs_and_the_manifest_only_grew(copy):
    root, before, mf = copy
    after = digests(root)
    assert {k for k in before if after.get(k) != before[k]} \
        == {"BENCHMARK.json"}
    added = sorted(set(after) - set(before))
    assert len(added) == 9 and all(k.startswith("chipbench/") for k in added)
    assert {k.split("/")[1] for k in added} == {
        "configs", "models", "reference", "work", "metrics", "traffic",
        "fixtures"}
    old = manifest.load_manifest()
    for group in ("configs", "workloads", "per_layer"):
        assert mf[group][: len(old[group])] == old[group]
    for was, now in zip(old["end_to_end"], mf["end_to_end"]):
        grown = dict(now)
        if "workloads" in was:
            n = len(was["workloads"])
            assert now["workloads"][:n] == was["workloads"]
            grown["workloads"] = now["workloads"][:n]
        assert grown == was
    assert {k: mf[k] for k in ("command", "paths", "run_seconds")} == \
        {k: old[k] for k in ("command", "paths", "run_seconds")}


def test_the_manifests_own_tests_pass_on_the_copy(copy):
    root = copy[0]
    done = in_copy(root, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                   "tests/chipbench/test_chipbench_manifest.py",
                   "tests/chipbench/test_chipbench_find.py", "-k",
                   "not names_in_use")
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    listed = in_copy(root, "-m", "pytest", "-q", "--collect-only", "-p",
                     "no:cacheprovider",
                     "tests/chipbench/test_chipbench_manifest.py").stdout
    assert CELL in listed and "gqa_attn_share.squid" in listed


def test_an_untraced_rehearsal_trains_the_strangers_model_and_is_correct(copy):
    root, _before, mf = copy
    obj = rehearse(root, mf, traced=0)
    assert set(obj["metrics"]) == {"setup_s", "train_tok_s"}
    # the program's rounds against the stranger's own reference
    assert all(row["value"] <= row["limit"] < 0.01
               for row in obj["compared"].values())


def test_a_traced_rehearsal_reads_the_strangers_scope_counter_and_work(copy):
    root, _before, mf = copy
    obj = rehearse(root, mf, traced=1)
    got = {k: v["value"] for k, v in obj["metrics"].items()}
    assert set(got) == {"mfu.squid", "gqa_attn_share.squid",
                        "kv_head_reuse.squid"}
    # fixtures/<cell>.plane.json, by hand: of a round's 20 us of operations
    # two of 4 us carry squid.gqa_attn innermost; 6 KV heads read for 12
    # query heads served; 192 tokens of 12 sequences in a 100 us window
    assert got["gqa_attn_share.squid"] == pytest.approx(40.0)
    assert got["kv_head_reuse.squid"] == pytest.approx(50.0)
    params = 2 * (2 * 32 * (32 + 16) + 3 * 32 * 64) + 32 * 64
    per_seq = (4 * params * 16 + 3 * 2 * 2 * 16 * 16 * 32
               + 2 * 3 * 2 * 2 * ((32 + 32) * 2 + (32 + 16) * 2) * 16)
    assert got["mfu.squid"] == pytest.approx(
        100 * per_seq * 12 / (100e-6 * 197e12))
    assert any(n.startswith("squid.gqa_attn:") for n, _ in
               obj["breakdown"]["device_ops"])
