"""CLI platform surface: launch / build / logs / diagnosis
(reference: cli/cli.py:18-76 subcommands; slave/client_diagnosis.py).

launch and diagnosis are exercised in-process via main(argv) — subprocess
startup pays jax import each time; in-process keeps the lane fast and still
covers the argparse wiring.
"""
import json
import sys

import pytest

from fedml_tpu.__main__ import main


def test_cli_build_and_manifest(tmp_path, capsys):
    src = tmp_path / "jobdir"
    src.mkdir()
    (src / "train.py").write_text("print('hi')\n")
    (src / "cfg.yaml").write_text("a: 1\n")
    rc = main(["build", "--source", str(src), "--entry", "train.py",
               "--dest", str(tmp_path / "dist")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["files"] == 2 and out["entry"] == "train.py"

    import tarfile

    with tarfile.open(out["package"]) as tar:
        names = tar.getnames()
    base = "jobdir"
    assert f"{base}/train.py" in names
    assert f"{base}/fedml_manifest.json" in names
    # manifest is generated into the tarball but cleaned from the source dir
    assert not (src / "fedml_manifest.json").exists()


def test_cli_build_missing_entry(tmp_path, capsys):
    src = tmp_path / "jobdir"
    src.mkdir()
    assert main(["build", "--source", str(src), "--entry", "nope.py",
                 "--dest", str(tmp_path)]) == 1


def test_cli_launch_runs_job_through_scheduler(tmp_path, capsys):
    job = tmp_path / "job.yaml"
    job.write_text("""
type: simulation
requirements: {}
config:
  data_args:
    dataset: synthetic
    extra: {synthetic_samples_per_client: 16}
  model_args: {model: lr}
  train_args:
    federated_optimizer: FedAvg
    client_num_in_total: 2
    client_num_per_round: 2
    comm_round: 1
    epochs: 1
    batch_size: 8
    learning_rate: 0.3
  validation_args: {frequency_of_the_test: 0}
""")
    db = str(tmp_path / "queue.db")
    rc = main(["launch", str(job), "--store", db, "--timeout", "300"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["status"] == "FINISHED", out
    # the durable queue recorded the terminal state
    from fedml_tpu.scheduler.store import JobStore

    jobs = JobStore(db).load_jobs()
    assert jobs and jobs[0]["status"] == "FINISHED"


def test_cli_logs(tmp_path, capsys):
    d = tmp_path / "log"
    d.mkdir()
    (d / "run1.events.jsonl").write_text('{"round": 0}\n{"round": 1}\n')
    rc = main(["logs", "--log-dir", str(d), "--list"])
    assert rc == 0
    assert "run1.events.jsonl" in json.loads(capsys.readouterr().out)["runs"]
    rc = main(["logs", "--log-dir", str(d), "--tail", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert '{"round": 1}' in out and '{"round": 0}' not in out
    assert main(["logs", "--log-dir", str(tmp_path / "missing")]) == 1


def test_cli_diagnosis(capsys):
    rc = main(["diagnosis"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0, report
    assert report["ok"] is True
    for required in ("jax", "wire_codec", "loopback_transport"):
        assert report["checks"][required]["ok"], report["checks"][required]
    # grpc/native may legitimately fail in minimal images, but must report
    assert "grpc_transport" in report["checks"]
    assert "native_lib" in report["checks"]


def test_bench_verb_parent_stays_jax_free():
    """`python -m fedml_tpu bench` starts bench.py as a CHILD, and a chip
    belongs to one process: the child gets it only while the parent never
    touched jax. Pin the laziness that makes that true — importing the
    package and the CLI module must not import jax."""
    import os
    import subprocess

    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, fedml_tpu, fedml_tpu.__main__; "
         "sys.exit(int('jax' in sys.modules))"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, (
        "import fedml_tpu / fedml_tpu.__main__ pulled in jax — the bench "
        f"verb's child could no longer get the chip\n{r.stderr[-1000:]}")
