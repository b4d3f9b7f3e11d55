"""Bench evidence integrity (round-4 verdict #1 and #3).

Only a ~2,000-char tail of bench stdout is archived and its last line is
parsed as JSON; a single fat line once lost the flagship fields to that cap.
These tests pin the two defenses: (a) the final line is a compact headline
that always fits, with the flagship fields leading; (b) a sub-benchmark
that raises is recorded under its `*_error` key AND makes main() exit
non-zero — a failed row never reads as a shorter result, and nothing is
retried on the strength of an error string.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def _fake_full(n_extra=200):
    full = {
        "metric": "fedavg_rounds_per_sec_100clients_resnet18_cifar10",
        "value": 1.2345,
        "unit": "rounds/sec",
        "vs_baseline": 123.45,
        "mfu_vs_spec_peak": 0.41,
        "round_time_ms": 810.0,
        "achieved_tflops": 80.6,
        "mfu_vs_matmul_peak": 0.5,
        "device_kind": "TPU v5e",
        "parity_acc_delta": 0.0123,
        "real_data_final_acc_digits_noniid": 0.93,
        "w1_mnist_lr_sp_rounds_per_sec": 55.0,
        "w4_hier_round_time_ms": 1007.7,
        "fedllm_1b_tokens_per_sec": 9000.0,
        "fedllm_1b_mfu_vs_spec_peak": 0.5,
        "fedllm_ceiling_params": 6738415616,
        "fedllm_ceiling_tokens_per_sec": 3344.0,
        "fedllm_ceiling_mfu_vs_spec_peak": 0.694,
        "fedllm_ceiling_config": "7b " * 60,
        "somerow_error": "JaxRuntimeError: DEADLINE_EXCEEDED " + "x" * 100,
    }
    # simulate a very fat full dict (the r04 line was ~4 KB and growing)
    for i in range(n_extra):
        full[f"aux_row_{i:03d}_note"] = "filler " * 10
    return full


def test_headline_fits_and_leads_with_flagship():
    full = _fake_full()
    head = bench._headline(full)
    line = json.dumps(head)
    assert len(line) <= bench._HEADLINE_BUDGET
    # mandatory contract keys + pointer to the full artifact
    for k in ("metric", "value", "unit", "vs_baseline", "full"):
        assert k in head
    assert head["full"] == os.path.join("chiprun_out", "bench_full.json")
    # the round-4 casualties must be IN the compact line
    assert head["mfu_vs_spec_peak"] == 0.41
    assert head["value"] == 1.2345
    assert head["fedllm_ceiling_mfu_vs_spec_peak"] == 0.694
    assert head["w1_mnist_lr_sp_rounds_per_sec"] == 55.0
    # error rows are candidates too — failures stay visible
    assert "somerow_error" in head
    # priority keys beat filler: no aux row may displace a flagship key
    assert not any(k.startswith("aux_row") for k in head)


def test_headline_budget_respected_even_with_huge_values():
    full = _fake_full()
    full["fedllm_ceiling_error"] = "err: " + "y" * 2000
    head = bench._headline(full, budget=600)
    assert len(json.dumps(head)) <= 600
    assert head["value"] == 1.2345


def test_run_rows_records_error_key_and_runs_each_row_once():
    calls = []

    def good():
        calls.append("good")
        return {"row": 42}

    def broken(arg):
        calls.append(arg)
        raise RuntimeError("DEADLINE_EXCEEDED: looks transient, is not "
                           "retried")

    out = bench._run_rows([("good_error", good),
                           ("broken_error", broken, "broken"),
                           ("after_error", lambda: {"after": 1})])
    assert out["row"] == 42 and out["after"] == 1
    assert out["broken_error"].startswith("RuntimeError: DEADLINE_EXCEEDED")
    assert "good_error" not in out
    assert calls == ["good", "broken"]        # once each, no retry


def _stub_bench(monkeypatch, tmp_path):
    """bench.main() over stub sub-benchmarks (the real ones take minutes):
    every `bench_*` returns one row, the flagship returns its tuple."""
    for name in dir(bench):
        if name.startswith("bench_") and name != "bench_tpu":
            monkeypatch.setattr(
                bench, name, lambda *a, _n=name, **k: {f"{_n}_row": 1})
    monkeypatch.setattr(bench, "bench_tpu",
                        lambda: (1.25, 0.8, 8e13, True, 1.5))
    monkeypatch.setattr(bench, "bench_torch_baseline", lambda n: 0.01)
    monkeypatch.setattr(bench, "measured_matmul_peak_tflops", lambda: 150.0)
    monkeypatch.setattr(bench, "FULL_OUT", str(tmp_path / "full.json"))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--quick"])


def test_main_quick_exits_zero_when_every_row_ran(monkeypatch, tmp_path,
                                                  capsys):
    _stub_bench(monkeypatch, tmp_path)
    assert bench.main() == 0
    head = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert head["value"] == 1.25
    full = json.loads((tmp_path / "full.json").read_text())
    assert full["bench_serving_spec_row"] == 1
    assert not [k for k in full if k.endswith("_error")]


def test_main_quick_raising_sub_benchmark_exits_nonzero(monkeypatch,
                                                        tmp_path, capsys):
    _stub_bench(monkeypatch, tmp_path)

    def boom(quick=False):
        raise ValueError("int8 pool refused by the compiler")

    monkeypatch.setattr(bench, "bench_serving_density", boom)
    assert bench.main() == 1
    captured = capsys.readouterr()
    head = json.loads(captured.out.strip().splitlines()[-1])
    # the failure is IN the archived line and in the full dict; the rows
    # after it still ran
    assert head["serving_density_error"].startswith("ValueError: int8 pool")
    full = json.loads((tmp_path / "full.json").read_text())
    assert full["serving_density_error"] == head["serving_density_error"]
    assert full["bench_live_loop_row"] == 1
    assert "serving_density_error" in captured.err


def test_main_flagship_failure_exits_nonzero(monkeypatch, tmp_path, capsys):
    _stub_bench(monkeypatch, tmp_path)

    def boom():
        raise RuntimeError("no chip")

    monkeypatch.setattr(bench, "bench_tpu", boom)
    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] is None and "no chip" in line["error"]


def test_no_retry_on_error_string_code_remains():
    for gone in ("_retrying", "_is_transient", "_TRANSIENT_MARKERS",
                 "_DETERMINISTIC_MARKERS"):
        assert not hasattr(bench, gone)
