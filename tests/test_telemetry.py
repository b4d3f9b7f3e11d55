"""End-to-end telemetry (ISSUE 2): metrics instruments, comm byte counters
across all three transports, trace-context stitching over a loopback
send→handle pair, Chrome-trace export from a tracked run, ring-buffer caps,
sink idempotency, and the report CLI verb."""
import json
import threading
import time
import uuid

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import mlops
from fedml_tpu.utils import metrics as mx
from fedml_tpu.utils.events import EventRecorder, recorder


# ------------------------------------------------------------- instruments
def test_counter_gauge_histogram_snapshot():
    mx.reset()
    try:
        mx.inc("t.c", 3)
        mx.inc("t.c")
        mx.set_gauge("t.g", 7.5)
        for v in (1e-5, 1e-3, 1e-3, 0.2):
            mx.observe("t.h", v)
        snap = mx.snapshot()
        assert snap["counters"]["t.c"] == 4
        assert snap["gauges"]["t.g"] == 7.5
        h = snap["histograms"]["t.h"]
        assert h["count"] == 4
        assert abs(h["sum"] - (1e-5 + 2e-3 + 0.2)) < 1e-9
        assert h["p50"] <= h["p99"] <= h["max"] + 1e-12
        # percentile-from-deltas path (what comm_bench uses)
        p = mx.percentile_from_counts(h["edges"], h["counts"], 0.5)
        assert p == h["p50"]
    finally:
        mx.reset()


def test_counter_shards_merge_across_threads():
    mx.reset()
    try:
        def worker():
            for _ in range(1000):
                mx.inc("t.threads")

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert mx.snapshot()["counters"]["t.threads"] == 4000
        # dead threads' shards fold into the base and are PRUNED — a
        # thread-per-request server must not grow one shard per request
        c = mx.counter("t.threads")
        assert c.value() == 4000
        assert len(c._shards) == 0
    finally:
        mx.reset()


def test_registry_rejects_kind_mismatch():
    mx.reset()
    try:
        mx.inc("t.kind")
        with pytest.raises(TypeError, match="already registered"):
            mx.observe("t.kind", 1.0)
    finally:
        mx.reset()


# --------------------------------------------------------- comm counters
def _pair(backend, run_id, **kw):
    from fedml_tpu.comm import FedCommManager
    from fedml_tpu.comm.manager import create_transport

    a = FedCommManager(create_transport(backend, 0, run_id, **kw), 0)
    b = FedCommManager(create_transport(backend, 1, run_id, **kw), 1)
    return a, b


@pytest.mark.parametrize("backend,prefix", [
    ("loopback", "loopback"), ("grpc", "grpc"), ("mqtt_s3", "broker")])
def test_comm_byte_counters_all_transports(backend, prefix):
    """Acceptance: non-zero comm byte counters for all three transports."""
    if backend == "grpc":
        pytest.importorskip("grpc")
    from fedml_tpu.comm import Message

    run_id = f"telem-{uuid.uuid4().hex[:6]}"
    kw = {}
    if backend == "grpc":
        import socket

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        p0, p1 = free_port(), free_port()
        kw = {"ip_table": {0: f"127.0.0.1:{p0}", 1: f"127.0.0.1:{p1}"}}
        a, b = (None, None)
        from fedml_tpu.comm import FedCommManager
        from fedml_tpu.comm.manager import create_transport

        a = FedCommManager(
            create_transport(backend, 0, run_id, port=p0, **kw), 0)
        b = FedCommManager(
            create_transport(backend, 1, run_id, port=p1, **kw), 1)
    else:
        a, b = _pair(backend, run_id)
    before = mx.snapshot()["counters"]
    got = threading.Event()
    payload = np.arange(64, dtype=np.float32)
    b.register_message_receive_handler(
        "w", lambda m: (np.asarray(m.get("w")), got.set()))
    a.run(background=True)
    b.run(background=True)
    try:
        a.send_message(Message("w", 0, 1).add("w", payload))
        assert got.wait(timeout=20)
    finally:
        a.stop()
        b.stop()
        if backend == "loopback":
            from fedml_tpu.comm.loopback import release_router

            release_router(run_id)
        if backend == "mqtt_s3":
            from fedml_tpu.comm.broker import release_broker

            release_broker(run_id)
    after = mx.snapshot()["counters"]

    def delta(leg):
        k = f"comm.{prefix}.{leg}"
        return after.get(k, 0) - before.get(k, 0)

    assert delta("msgs_sent") >= 1
    assert delta("msgs_recv") >= 1
    assert delta("bytes_sent") >= payload.nbytes
    assert delta("bytes_recv") >= payload.nbytes
    hists = mx.snapshot()["histograms"]
    assert hists[f"comm.{prefix}.serialize_s"]["count"] >= 1
    assert hists[f"comm.{prefix}.publish_s"]["count"] >= 1


def test_broker_blob_path_counts_payload_bytes():
    """Above blob_threshold the payload rides the blob plane; counters must
    still see the full canonical frame, and the blob_puts counter ticks."""
    from fedml_tpu.comm import FedCommManager, Message
    from fedml_tpu.comm.broker import release_broker

    run_id = f"telem-{uuid.uuid4().hex[:6]}"
    before = mx.snapshot()["counters"]
    a, b = _pair("mqtt_s3", run_id, blob_threshold=1024)
    got = threading.Event()
    payload = np.arange(4096, dtype=np.float32)     # 16KB > 1KB threshold
    b.register_message_receive_handler("w", lambda m: got.set())
    a.run(background=True)
    b.run(background=True)
    try:
        a.send_message(Message("w", 0, 1).add("w", payload))
        assert got.wait(timeout=20)
    finally:
        a.stop()
        b.stop()
        release_broker(run_id)
    after = mx.snapshot()["counters"]
    assert (after.get("comm.broker.blob_puts", 0)
            - before.get("comm.broker.blob_puts", 0)) == 1
    assert (after.get("comm.broker.bytes_sent", 0)
            - before.get("comm.broker.bytes_sent", 0)) >= payload.nbytes
    assert (after.get("comm.broker.bytes_recv", 0)
            - before.get("comm.broker.bytes_recv", 0)) >= payload.nbytes


# ------------------------------------------------------- trace propagation
def test_trace_stitches_across_loopback_send_handle():
    """A send inside a span and the receiver's handler span share one
    trace_id; the handle span's parent chain leads back to the sender."""
    from fedml_tpu.comm import FedCommManager, Message
    from fedml_tpu.comm.loopback import LoopbackTransport, release_router

    run_id = f"telem-{uuid.uuid4().hex[:6]}"
    a = FedCommManager(LoopbackTransport(0, run_id), 0)
    b = FedCommManager(LoopbackTransport(1, run_id), 1)
    got = threading.Event()
    inner: list = []

    def handler(_msg):
        # spans opened INSIDE the handler inherit the adopted trace too
        with recorder.span("handler.work"):
            pass
        inner.append(True)
        got.set()

    b.register_message_receive_handler("ping", handler)
    a.run(background=True)
    b.run(background=True)
    n0 = len(recorder.spans)
    try:
        with recorder.span("round.driver") as root:
            a.send_message(Message("ping", 0, 1))
            assert got.wait(timeout=10)
        time.sleep(0.05)   # let the handle span close
    finally:
        a.stop()
        b.stop()
        release_router(run_id)
    spans = {s.name: s for s in recorder.spans[n0:]}
    send = spans["comm.send.ping"]
    handle = spans["comm.handle.ping"]
    work = spans["handler.work"]
    assert send.trace_id == root.trace_id
    assert handle.trace_id == root.trace_id
    assert work.trace_id == root.trace_id
    # the handle span's parent is the SEND span on the other side
    assert handle.parent_id == send.span_id
    assert work.parent_id == handle.span_id


def test_unstamped_message_gets_fresh_trace():
    from fedml_tpu.comm.message import ARG_TRACE_ID, Message

    m = Message("x", 0, 1)
    m.stamp_trace()           # no active span -> no headers
    assert ARG_TRACE_ID not in m.params
    assert m.trace_context() == (None, None)


# ------------------------------------------- tracked run -> chrome trace
def test_tracked_run_exports_valid_chrome_trace(tmp_path):
    """Acceptance: a tracked run produces a Chrome-trace JSON whose
    traceEvents validate and contain round, comm, and serving spans, with
    the comm send/handle pair sharing a stitched trace_id; the metrics
    snapshot shows a serving request-latency histogram."""
    import urllib.request

    import jax

    from fedml_tpu.comm import FedCommManager, Message
    from fedml_tpu.comm.loopback import LoopbackTransport, release_router
    from fedml_tpu.models import hub
    from fedml_tpu.serving import FedMLInferenceRunner, JaxPredictor
    from fedml_tpu.simulation.simulator import Simulator

    cfg = fedml_tpu.init(config={
        "data_args": {"dataset": "synthetic",
                      "extra": {"synthetic_samples_per_client": 16}},
        "model_args": {"model": "lr"},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 2, "client_num_per_round": 2,
                       "comm_round": 2, "epochs": 1, "batch_size": 8,
                       "learning_rate": 0.3},
        "validation_args": {"frequency_of_the_test": 0},
        "comm_args": {"backend": "sp"},
        "tracking_args": {"enable_tracking": True,
                          "log_file_dir": str(tmp_path),
                          "run_name": "telem-accept"},
    })
    n_sinks = len(recorder.sinks)
    mlops.init(cfg)
    try:
        # round spans
        Simulator(cfg).run(2)

        # comm spans over a loopback pair, stitched under one driver span
        run_id = f"telem-{uuid.uuid4().hex[:6]}"
        a = FedCommManager(LoopbackTransport(0, run_id), 0)
        b = FedCommManager(LoopbackTransport(1, run_id), 1)
        got = threading.Event()
        b.register_message_receive_handler("ping", lambda m: got.set())
        a.run(background=True)
        b.run(background=True)
        try:
            with recorder.span("round.drive"):
                a.send_message(Message("ping", 0, 1))
                assert got.wait(timeout=10)
            time.sleep(0.05)
        finally:
            a.stop()
            b.stop()
            release_router(run_id)

        # serving spans + request-latency histogram over real HTTP
        model = hub.create("lr", 3)
        params = hub.init_params(model, (8,), jax.random.key(0))
        runner = FedMLInferenceRunner(
            JaxPredictor(model.apply, params), port=0)
        runner.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{runner.port}/predict",
                data=json.dumps(
                    {"inputs": np.zeros((2, 8)).tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req, timeout=10).read())
            assert len(out["predictions"]) == 2
        finally:
            runner.stop()
    finally:
        mlops.finish()
        del recorder.sinks[n_sinks:]

    snap = mx.snapshot()
    h = snap["histograms"]["serving.request_s"]
    assert h["count"] >= 1 and h["p50"] > 0
    assert snap["histograms"]["serving.predict.compile_s"]["count"] >= 1

    trace_path = tmp_path / "telem-accept.trace.json"
    assert trace_path.exists()
    doc = json.loads(trace_path.read_text())
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert {"ph", "pid", "name"} <= set(e)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert "trace_id" in e["args"]
    by_cat = {e.get("cat") for e in evs if e["ph"] == "X"}
    assert {"round", "comm", "serving"} <= by_cat
    # named tracks exist
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {"round", "comm", "serving"} <= names
    # stitched loopback pair inside the exported artifact
    send = next(e for e in evs if e["name"] == "comm.send.ping")
    handle = next(e for e in evs if e["name"] == "comm.handle.ping")
    assert send["args"]["trace_id"] == handle["args"]["trace_id"]
    assert handle["args"]["parent_id"] == send["args"]["span_id"]
    # the events jsonl got the end-of-run report row
    rows = [json.loads(l) for l in
            (tmp_path / "telem-accept.events.jsonl").read_text().splitlines()]
    report = [r for r in rows if "report" in r]
    assert report and "spans" in report[-1]["report"]
    assert "counters" in report[-1]["report"]["metrics"]


@pytest.mark.parametrize("backend,clients", [("sp", 2), ("xla", 8)])
def test_retrace_metric_round_fn(backend, clients):
    """PR 1's retrace guard as an always-on metric: a warm simulator shows
    exactly one compiled round program and zero retraces — on the mesh
    path too, where un-pinned first-round state once bought a second
    compile (the program's own outputs come back mesh-committed)."""
    mx.reset()
    try:
        cfg = fedml_tpu.init(config={
            "data_args": {"dataset": "synthetic",
                          "extra": {"synthetic_samples_per_client": 16}},
            "model_args": {"model": "lr"},
            "train_args": {"federated_optimizer": "FedAvg",
                           "client_num_in_total": clients,
                           "client_num_per_round": clients, "comm_round": 3,
                           "epochs": 1, "batch_size": 8,
                           "learning_rate": 0.3},
            "validation_args": {"frequency_of_the_test": 0},
            "comm_args": {"backend": backend},
        })
        from fedml_tpu.simulation.simulator import Simulator

        Simulator(cfg).run(3)
        snap = mx.snapshot()
        assert snap["gauges"]["xla.compiles.round_fn"] == 1
        assert snap["counters"].get("xla.retraces.round_fn", 0) == 0
    finally:
        mx.reset()


# ----------------------------------------------------- events.py satellites
def test_recorder_ring_cap_keeps_exact_summary():
    rec = EventRecorder(max_rows=10)
    for i in range(25):
        with rec.span("s"):
            pass
        rec.log({"i": i})
    assert len(rec.spans) == 10
    assert len(rec.metrics) == 10
    assert rec.summary()["s"]["count"] == 25      # exact despite eviction
    assert rec.metrics[-1]["i"] == 24
    assert rec.metrics[2:4] == [{"i": 17}, {"i": 18}]   # slicing preserved


def test_dump_rows_are_orderable(tmp_path):
    rec = EventRecorder()
    with rec.span("a"):
        time.sleep(0.01)
    with rec.span("b"):
        pass
    p = tmp_path / "dump.jsonl"
    rec.dump(str(p))
    rows = [json.loads(l) for l in p.read_text().splitlines()
            if "span" in l]
    spans = [r for r in rows if "span" in r]
    assert all("t" in r and "start" in r for r in spans)
    assert spans[0]["start"] < spans[1]["start"]
    assert spans[0]["t"] < spans[1]["t"]
    assert abs(spans[0]["t"] - time.time()) < 60   # wall-clock scale


def test_sysperf_start_primes_cpu_percent(monkeypatch):
    import psutil

    from fedml_tpu.utils.sysperf import SysPerfMonitor

    calls = []
    orig = psutil.cpu_percent
    monkeypatch.setattr(psutil, "cpu_percent",
                        lambda interval=None: calls.append(interval)
                        or orig(interval=interval))
    mon = SysPerfMonitor(interval=60.0).start()
    try:
        # the priming sample happened at start(), before any loop tick
        assert calls and calls[0] is None
    finally:
        mon.stop()


# --------------------------------------------------------- sink satellites
def test_attach_from_config_idempotent_across_reinit(tmp_path):
    from fedml_tpu.utils.sinks import attach_from_config

    n0 = len(recorder.sinks)
    cfg = fedml_tpu.init(config={
        "tracking_args": {"enable_tracking": True,
                          "log_file_dir": str(tmp_path),
                          "run_name": "idem"},
    })
    try:
        # fedml_tpu.init attached this run's JsonlSink already
        assert len(recorder.sinks) == n0 + 1
        # repeated mlops.init must not double-attach (or double-log)
        mlops.init(cfg)
        mlops.init(cfg)
        again = attach_from_config(cfg)
        assert again == []
        assert len(recorder.sinks) == n0 + 1
    finally:
        mlops.finish()
        del recorder.sinks[n0:]


def test_collect_logs_drains_broker_tail_batch(tmp_path):
    """Rows buffered below batch_size only ship on flush; flush_sinks must
    push the tail batch and collect_logs must drain it."""
    from fedml_tpu.comm.broker import release_broker
    from fedml_tpu.utils.sinks import (
        BrokerLogSink, collect_logs, flush_sinks,
    )

    bid = f"telem-logs-{uuid.uuid4().hex[:6]}"
    run = "tailrun"
    sink = BrokerLogSink(run, broker_id=bid, batch_size=50)
    recorder.sinks.append(sink)
    try:
        recorder.log({"acc": 0.1})
        recorder.log({"acc": 0.2})
        # nothing shipped yet (2 < 50) — the tail batch is in the buffer
        assert collect_logs(run, broker_id=bid) == []
        flush_sinks()
        rows = collect_logs(run, broker_id=bid)
        assert [r.get("acc") for r in rows] == [0.1, 0.2]
        assert all(r["kind"] == "metrics" for r in rows)
    finally:
        recorder.sinks.remove(sink)
        release_broker(bid)


# ------------------------------------------------------------- report CLI
def test_report_cli_verb(tmp_path, capsys):
    from fedml_tpu.__main__ import main as cli_main

    cfg = fedml_tpu.init(config={
        "tracking_args": {"enable_tracking": True,
                          "log_file_dir": str(tmp_path),
                          "run_name": "cli-report"},
    })
    n0 = len(recorder.sinks)
    mlops.init(cfg)
    try:
        with mlops.event("train", round=0):
            time.sleep(0.005)
        mlops.log({"acc": 0.9})
        mx.inc("t.report_cli")       # so the end-of-run snapshot is non-empty
    finally:
        mlops.finish()
        del recorder.sinks[n0:]
    rc = cli_main(["report", "--log-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "train" in out and "spans:" in out
    assert "counters:" in out or "histograms:" in out
    assert "cli-report.trace.json" in out


# ------------------------------------------------------- mlops facade glue
def test_metrics_snapshot_facade():
    mx.inc("t.facade")
    snap = mlops.metrics_snapshot()
    assert snap["counters"]["t.facade"] >= 1
