"""Serving fleet robustness (ISSUE 9): hot adapter swap, load-shedding
admission control, streaming that survives failover.

The contracts under test:
- hot swap: adapter VALUES swap between decode iterations — no KV-cache
  teardown, no retrace, token-identical to a replica built on the new
  adapters; structure/shape changes and version regressions are refused.
- drain: stop(drain=True) lets in-flight decodes finish; submits during
  teardown are refused, not hung.
- fleet: rolling v1->v2 update under sustained load drops ZERO requests;
  per-request version pinning 409s on the wrong replica and reroutes at
  the gateway; a SUSPECT replica re-probes and REJOINS the pool.
- overload: above the shed watermark the gateway answers 429 +
  Retry-After instead of queueing.
- streaming: SSE end-to-end; a replica chaos-killed mid-stream is
  transparently re-served from token 0 on the survivor for greedy
  requests (total output byte-identical to an unkilled run) and surfaces
  a clean terminal error for sampled requests — never a fake `done`.

Module-scoped fixtures share the jit-heavy engines (tier-1 budget
discipline — see test_serving_engine.py)."""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.inference_runner import FedMLInferenceRunner
from fedml_tpu.serving.predictor import GreedyLMPredictor, StaleVersion
from fedml_tpu.serving.scheduler import Deployment, InferenceGateway
from fedml_tpu.utils import metrics as _mx
from fedml_tpu.utils.artifacts import FileArtifactStore, adapter_name

V, D, L, H, FF = 64, 32, 1, 2, 64
MAXLEN = 32


@pytest.fixture(scope="module")
def setup():
    import jax.numpy as jnp

    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    a1 = lora_init(jax.random.key(1), params, rank=2, a_std=0.3)
    a1 = jax.tree.map(lambda a: a + 0.05 * np.ones(a.shape, a.dtype), a1)
    a2 = jax.tree.map(lambda a: a * -1.2 + 0.07, a1)
    return model, params, a1, a2


@pytest.fixture(scope="module")
def want(setup):
    """Per-request reference outputs under a1 and a2 (one compile each)."""
    model, params, a1, a2 = setup
    p1 = GreedyLMPredictor(model, params, adapters=a1, max_len=MAXLEN,
                           kv_cache=True)
    p2 = GreedyLMPredictor(model, params, adapters=a2, max_len=MAXLEN,
                           kv_cache=True)
    return p1, p2


@pytest.fixture(scope="module")
def eng(setup):
    """Shared engine on a1 — the swap test moves it to a2/v-next; later
    tests in this module must not assume a1 outputs. The drain test
    (deliberately last engine user) stops it."""
    model, params, a1, _a2 = setup
    e = DecodeEngine(model, params, adapters=a1, n_slots=2,
                     max_len=MAXLEN).start()
    yield e
    e.stop()


def _prompt(n=6, seed=0):
    return np.random.RandomState(seed).randint(1, V, n).tolist()


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _sse(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        ctype = r.headers.get("Content-Type")
        raw = r.read().decode()
    events = [json.loads(ln[len("data:"):]) for ln in raw.split("\n\n")
              if ln.strip().startswith("data:")]
    return ctype, events


# ------------------------------------------------------------- hot swap
def test_engine_hot_swap_token_identical_no_retrace(setup, want, eng):
    """Swapped-in adapters serve EXACTLY what a replica built on them
    serves, with zero new compiles — and an in-flight request straddling
    the swap completes (the zero-dropped primitive)."""
    _model, _params, _a1, a2 = setup
    p1, p2 = want
    prompt = _prompt()
    assert eng.submit(prompt, 5).result(timeout=120) == p1.predict(
        {"tokens": prompt, "max_new_tokens": 5})["generated_tokens"]
    counts = eng.program_counts()
    inflight = eng.submit(prompt, 20)          # straddles the swap
    ver = eng.swap_adapters(a2)
    assert ver == 1 and eng.model_version == 1
    assert len(inflight.result(timeout=120)) == 20   # finished, not errored
    got = eng.submit(prompt, 5).result(timeout=120)
    assert got == p2.predict(
        {"tokens": prompt, "max_new_tokens": 5})["generated_tokens"]
    assert eng.program_counts() == counts, "swap retraced a program"
    assert _mx.snapshot()["gauges"]["serving.model_version"] == 1


def test_swap_refusals(setup, eng):
    """Structure/shape changes and version regressions are refused; an
    adapterless engine has nothing to swap."""
    model, params, _a1, a2 = setup
    # structural change (a target dropped) would retrace -> refused
    bad = {k: v for k, v in a2.items() if "wq" not in k}
    with pytest.raises(ValueError, match="structure"):
        eng.swap_adapters(bad)
    # shape change refused, leaf named
    bad = dict(a2)
    key0 = next(iter(a2))
    bad[key0] = {"a": np.zeros((L, D, 4), np.float32),
                 "b": a2[key0]["b"]}
    with pytest.raises(ValueError, match="compile-time"):
        eng.swap_adapters(bad)
    # non-monotonic version refused (the engine is at v1 from the test
    # above; module order is load-bearing, as documented on the fixture)
    with pytest.raises(ValueError, match="monotonic"):
        eng.swap_adapters(a2, version=1)
    # adapterless engine refuses loudly
    e2 = DecodeEngine(model, params, n_slots=1, max_len=MAXLEN)
    with pytest.raises(ValueError, match="without adapters"):
        e2.swap_adapters(a2)


def test_ticket_stream_matches_result(eng):
    prompt = _prompt(7, seed=3)
    t = eng.submit(prompt, 6)
    assert list(t.stream(timeout=120)) == t.result(timeout=1)


def test_engine_drain_lets_inflight_finish(setup, eng):
    """stop(drain=True): a decoding request finishes (never errored);
    submits during/after teardown are refused. Last engine test — it
    stops the shared engine."""
    prompt = _prompt()
    t = eng.submit(prompt, 24)
    eng.stop(drain=True, drain_timeout_s=60)
    assert len(t.result(timeout=1)) == 24      # already done, not errored
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit(prompt, 2)


# ---------------------------------------------------------------- fleet
@pytest.fixture(scope="module")
def fleet(setup):
    """2 engine-backed replicas on a1 + adopted deployment + gateway.
    The rolling-update test moves the fleet to v2; later tests see v2."""
    model, params, a1, _a2 = setup
    runners = [FedMLInferenceRunner(
        GreedyLMPredictor(model, params, adapters=a1, max_len=MAXLEN,
                          kv_cache=True, decode_slots=2),
        port=0).start() for _ in range(2)]
    dep = Deployment.adopt([f"http://127.0.0.1:{r.port}" for r in runners],
                           probation_deadline_s=2.0)
    gw = InferenceGateway(dep, scale_interval=30, retry_backoff_s=0.02)
    gw.start()
    yield runners, dep, gw
    gw.stop()
    for r in runners:
        r.stop()


def test_rolling_update_zero_dropped_under_load(tmp_path, setup, want,
                                                fleet):
    """THE acceptance bar: sustained concurrent traffic across a v1->v2
    rolling adapter update — zero non-2xx (nothing is shed: no watermark
    armed), both replicas report v2, and post-swap output matches a
    replica built on a2."""
    _model, _params, _a1, a2 = setup
    _p1, p2 = want
    runners, dep, gw = fleet
    url = f"http://127.0.0.1:{gw.port}/predict"
    prompt = _prompt()
    store = FileArtifactStore(str(tmp_path))
    store.put(adapter_name(2), jax.tree.map(np.asarray, a2))
    codes: list = []
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                codes.append(_post(url, {"tokens": prompt,
                                         "max_new_tokens": 4})[0])
            except urllib.error.HTTPError as e:
                codes.append(e.code)

    threads = [threading.Thread(target=load, daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        updated = dep.rolling_update(store, adapter_name(2), version=2,
                                     timeout=60)
    finally:
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert len(updated) == 2
    assert codes and all(c == 200 for c in codes), (
        f"{sum(c != 200 for c in codes)}/{len(codes)} non-2xx during "
        "rolling update")
    assert dep.versions() == {"adopted-0": 2, "adopted-1": 2}
    _code, out = _post(url, {"tokens": prompt, "max_new_tokens": 5})
    assert out["generated_tokens"] == p2.predict(
        {"tokens": prompt, "max_new_tokens": 5})["generated_tokens"]


def test_version_pinning_409_and_gateway_reroute(fleet):
    """A pinned request 409s on the wrong replica (replica stays READY);
    the gateway reroutes a pin to a replica that serves it, and surfaces
    409 only when nobody does. The fleet is at v2 (test above)."""
    runners, dep, gw = fleet
    url = f"http://127.0.0.1:{gw.port}/predict"
    prompt = _prompt()
    # the whole fleet serves v2 -> pin v2 succeeds
    code, _ = _post(url, {"tokens": prompt, "max_new_tokens": 2,
                          "model_version": 2})
    assert code == 200
    # make the fleet mixed: replica 0 alone moves to v3 via /swap —
    # after this, pin v3 must still answer 200 through the gateway
    # (reroute), pin v2 must also answer 200 (the other replica)
    info0 = dep.replica_info(dep.replicas[0])
    assert info0["model_version"] == 2
    pred0 = runners[0].predictor
    pred0.swap_adapters(jax.tree.map(lambda a: a * 0.5, pred0.adapters),
                        version=3)
    before = _mx.snapshot()["counters"].get(
        "serving.gateway_pin_reroutes", 0)
    # routing ties break round-robin, so WHICH replica a single pinned
    # request starts on depends on the module's acquire-count parity —
    # drive pin 3 until one starts on the v2 replica and reroutes (two
    # consecutive requests cannot both start on the v3 replica unless
    # one of them already rerouted)
    for pin in (3, 2, 3, 3, 3, 3):
        code, _ = _post(url, {"tokens": prompt, "max_new_tokens": 2,
                              "model_version": pin})
        assert code == 200, (pin, code)
        if _mx.snapshot()["counters"].get(
                "serving.gateway_pin_reroutes", 0) > before:
            break
    assert _mx.snapshot()["counters"].get(
        "serving.gateway_pin_reroutes", 0) > before
    # a version nobody serves surfaces 409 (never 502/500, and the
    # replicas stay READY — pins must not look like failures)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url, {"tokens": prompt, "max_new_tokens": 2,
                    "model_version": 99})
    assert ei.value.code == 409
    assert len(dep.ready_replicas()) == 2
    # direct-to-replica pin mismatch is a 409 with the served version
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{dep.replicas[1].endpoint}/predict",
              {"tokens": prompt, "max_new_tokens": 2, "model_version": 99})
    assert ei.value.code == 409
    assert json.loads(ei.value.read())["model_version"] == 2
    # predictor-level contract: StaleVersion is an InvalidRequest
    with pytest.raises(StaleVersion):
        runners[1].predictor.predict(
            {"tokens": prompt, "max_new_tokens": 2, "model_version": 99})


def test_garbage_body_is_400_and_never_drains_the_pool(fleet):
    """Non-JSON and non-object bodies are the CLIENT's error (400): a
    500 would let one garbage request mark every replica it is retried
    on SUSPECT and empty a 2-replica pool."""
    _runners, dep, gw = fleet
    url = f"http://127.0.0.1:{gw.port}/predict"
    for body in (b"not json{{{", b"[1, 2, 3]", b'"hi"', b"42"):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        ei.value.read()
        assert ei.value.code == 400, (body, ei.value.code)
    assert len(dep.ready_replicas()) == 2


def test_replica_sse_stream_and_info(fleet):
    """Replica-direct SSE: per-token events then a done event matching
    the non-streamed response; /info carries version + load signals;
    stream TTFT histogram records."""
    runners, _dep, _gw = fleet
    url = f"http://127.0.0.1:{runners[1].port}"
    prompt = _prompt(8, seed=5)
    _code, want = _post(url + "/predict",
                        {"tokens": prompt, "max_new_tokens": 6})
    ctype, events = _sse(url + "/predict",
                         {"tokens": prompt, "max_new_tokens": 6,
                          "stream": True})
    assert ctype == "text/event-stream"
    toks = [e["token"] for e in events if "token" in e]
    assert [e.get("index") for e in events if "token" in e] == list(range(6))
    assert toks == want["generated_tokens"]
    assert events[-1]["done"] is True
    assert events[-1]["generated_tokens"] == want["generated_tokens"]
    assert _mx.snapshot()["histograms"]["serving.stream_ttft"]["count"] >= 1
    with urllib.request.urlopen(url + "/info", timeout=30) as r:
        info = json.loads(r.read())
    assert info["model_version"] == 2 and info["draining"] is False
    assert info["queue_depth"] == 0


# ------------------------------------------------- probation / shedding
class _ToggleReplica:
    """Stub replica whose health is a flag: when down, /ready answers 503
    and /predict 500 — the transient-failure shape probation exists for.
    No jax; per-test cheap."""

    def __init__(self, delay_s: float = 0.0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        stub = self
        self.up = True
        self.delay_s = delay_s

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                self._send(200 if stub.up else 503, {"up": stub.up})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                if not stub.up:
                    self._send(500, {"error": "flaking"})
                    return
                if stub.delay_s:
                    time.sleep(stub.delay_s)
                self._send(200, {"generated_tokens": [1]})

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def test_probation_flap_then_recover():
    """SUSPECT -> probation -> recovered: one bad window pulls the
    replica from rotation but KEEPS probing; when it answers /ready again
    it rejoins ready_replicas() — mark_dead-forever was the bug."""
    stub = _ToggleReplica()
    dep = Deployment.adopt([f"http://127.0.0.1:{stub.port}"],
                           probation_deadline_s=5.0, probe_backoff_s=0.02)
    gw = InferenceGateway(dep, scale_interval=30, retry_backoff_s=0.01)
    gw.start()
    url = f"http://127.0.0.1:{gw.port}/predict"
    try:
        assert _post(url, {"x": 1})[0] == 200
        stub.up = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, {"x": 1})
        assert ei.value.code in (502, 503)     # suspect: out of rotation
        assert dep.replicas[0].state == "SUSPECT"
        assert dep.ready_replicas() == []
        assert _mx.snapshot()["counters"]["serving.replica_suspects"] == 1
        stub.up = True                          # the flap ends
        deadline = time.monotonic() + 5
        while (dep.replicas[0].state != "READY"
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert dep.replicas[0].state == "READY", "never recovered"
        assert _mx.snapshot()["counters"]["serving.replica_recoveries"] == 1
        assert _post(url, {"x": 1})[0] == 200   # back in rotation
        # a flap that does NOT end goes DEAD after the deadline
        stub.up = False
        try:
            _post(url, {"x": 1})
        except urllib.error.HTTPError:
            pass
        deadline = time.monotonic() + 8
        while (dep.replicas[0].state != "DEAD"
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert dep.replicas[0].state == "DEAD"
    finally:
        gw.stop()
        stub.stop()


def test_gateway_sheds_429_with_retry_after():
    """Above shed_watermark x ready replicas, new requests get a FAST
    429 + Retry-After (serving.shed_total counts them); below it they
    serve normally. Overload degrades to refusal, not timeout."""
    stub = _ToggleReplica(delay_s=0.25)
    dep = Deployment.adopt([f"http://127.0.0.1:{stub.port}"])
    gw = InferenceGateway(dep, scale_interval=30, shed_watermark=2.0,
                          retry_after_s=1.5)
    gw.start()
    url = f"http://127.0.0.1:{gw.port}/predict"
    results: list = []
    lock = threading.Lock()

    def hit():
        t0 = time.perf_counter()
        try:
            code = _post(url, {"x": 1})[0]
            hdr = None
        except urllib.error.HTTPError as e:
            code = e.code
            hdr = e.headers.get("Retry-After")
            e.read()
        with lock:
            results.append((code, hdr, time.perf_counter() - t0))

    try:
        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        codes = [c for c, _h, _dt in results]
        sheds = [(c, h, dt) for c, h, dt in results if c == 429]
        assert sheds, f"nothing shed: {codes}"
        assert codes.count(200) >= 1
        assert set(codes) <= {200, 429}, codes
        for _c, hdr, dt in sheds:
            assert hdr == "2"                  # ceil(retry_after_s=1.5)
            assert dt < 0.2, f"shed was not fast: {dt:.3f}s"
        assert _mx.snapshot()["counters"]["serving.shed_total"] == len(sheds)
        # below the watermark again: normal service
        assert _post(url, {"x": 1})[0] == 200
    finally:
        gw.stop()
        stub.stop()


# ------------------------------------------------- mid-stream failover
def test_midstream_chaos_kill_greedy_reserved_seeded_errors(setup, want):
    """Chaos-kill a replica mid-stream (FaultSpec.replica_kill): the
    greedy stream is transparently re-served by the survivor with total
    output TOKEN-IDENTICAL to an unkilled run; a sampled stream surfaces
    a terminal 503-coded error event and never a fake `done`."""
    from fedml_tpu.comm.chaos import FaultSpec

    model, params, a1, _a2 = setup
    p1, _p2 = want
    prompt = _prompt()
    want_toks = p1.predict({"tokens": prompt, "max_new_tokens": 12}
                           )["generated_tokens"]

    def mk(chaos=None):
        return FedMLInferenceRunner(
            GreedyLMPredictor(model, params, adapters=a1, max_len=MAXLEN,
                              kv_cache=True, decode_slots=2),
            port=0, chaos=chaos, chaos_rank=0).start()

    doomed = mk(chaos=FaultSpec(replica_kill={0: 4}))
    survivor = mk()
    dep = Deployment.adopt(
        [f"http://127.0.0.1:{doomed.port}",
         f"http://127.0.0.1:{survivor.port}"], probation_deadline_s=0.5)
    gw = InferenceGateway(dep, scale_interval=30, retry_backoff_s=0.01)
    gw.start()
    url = f"http://127.0.0.1:{gw.port}/predict"
    try:
        # greedy: every stream completes identically, whether or not it
        # hit the doomed replica; loop until the kill provably fired
        fired = False
        for _ in range(6):
            _ctype, events = _sse(url, {"tokens": prompt,
                                        "max_new_tokens": 12,
                                        "stream": True})
            toks = [e["token"] for e in events if "token" in e]
            assert events[-1].get("done") is True
            assert toks == want_toks, "failover stream diverged"
            if _mx.snapshot()["counters"].get("serving.stream_failovers"):
                fired = True
                break
        assert fired, "replica_kill never fired"
        assert dep.replicas[0].state in ("SUSPECT", "DEAD")

        # sampled: a second doomed replica; the cut surfaces as a clean
        # terminal error (503 code in-band or on the response), with no
        # done event — half a sampled stream must never look complete
        doomed2 = mk(chaos=FaultSpec(replica_kill={0: 2}))
        dep2 = Deployment.adopt(
            [f"http://127.0.0.1:{doomed2.port}"], probation_deadline_s=0.5)
        gw2 = InferenceGateway(dep2, scale_interval=30,
                               retry_backoff_s=0.01)
        gw2.start()
        url2 = f"http://127.0.0.1:{gw2.port}/predict"
        try:
            saw_clean_error = False
            for _ in range(4):
                try:
                    _ctype, events = _sse(
                        url2, {"tokens": prompt, "max_new_tokens": 10,
                               "stream": True, "temperature": 2.0,
                               "seed": 7})
                except urllib.error.HTTPError as e:
                    assert e.code == 503
                    saw_clean_error = True
                    break
                if any("error" in e for e in events):
                    assert not any(e.get("done") for e in events), events
                    assert events[-1]["code"] == 503
                    saw_clean_error = True
                    break
                assert events[-1].get("done") is True
            assert saw_clean_error, "sampled kill never surfaced"
        finally:
            gw2.stop()
            doomed2.stop()
    finally:
        gw.stop()
        doomed.stop()
        survivor.stop()


def test_midstream_kill_during_version_skew_continues_stream(setup, want):
    """The ISSUE 15 live-loop race, pinned deterministically: a replica
    dies mid-stream while the only survivor already serves a NEWER
    adapter version (mid-rolling-update skew). The greedy replay
    diverges inside the delivered prefix; an UNPINNED stream must then
    be CONTINUED — prompt + delivered tokens re-issued under the new
    weights — so the client gets prefix-under-v1 + greedy
    continuation-under-v2 with a real `done`, exactly what an in-place
    hot swap mid-stream would have produced. Zero non-2xx through
    version churn rides this path."""
    from fedml_tpu.comm.chaos import FaultSpec
    from fedml_tpu.utils import metrics as _mx

    model, params, a1, a2 = setup
    p1, p2 = want
    prompt = _prompt()
    want1 = p1.predict({"tokens": prompt, "max_new_tokens": 12}
                       )["generated_tokens"]
    want2 = p2.predict({"tokens": prompt, "max_new_tokens": 12}
                       )["generated_tokens"]
    # precondition: the versions must disagree inside the kill window,
    # or the replay would simply dedupe (that path is the test above)
    assert want1[:4] != want2[:4], "fixture adapters too similar"

    doomed = FedMLInferenceRunner(
        GreedyLMPredictor(model, params, adapters=a1, max_len=MAXLEN,
                          kv_cache=True, decode_slots=2),
        port=0, chaos=FaultSpec(replica_kill={0: 4}), chaos_rank=0).start()
    survivor = FedMLInferenceRunner(
        GreedyLMPredictor(model, params, adapters=a2, max_len=MAXLEN,
                          kv_cache=True, decode_slots=2), port=0).start()
    dep = Deployment.adopt(
        [f"http://127.0.0.1:{doomed.port}",
         f"http://127.0.0.1:{survivor.port}"], probation_deadline_s=0.5)
    gw = InferenceGateway(dep, scale_interval=30, retry_backoff_s=0.01)
    gw.start()
    url = f"http://127.0.0.1:{gw.port}/predict"
    try:
        cut_toks = cut_events = None
        for _ in range(6):
            _ctype, events = _sse(url, {"tokens": prompt,
                                        "max_new_tokens": 12,
                                        "stream": True})
            toks = [e["token"] for e in events if "token" in e]
            assert events[-1].get("done") is True, events[-1]
            assert len(toks) == 12
            if _mx.snapshot()["counters"].get(
                    "serving.stream_continuations"):
                cut_toks, cut_events = toks, events
                break
            # an uncut stream is wholly v1 (doomed) or wholly v2
            assert toks in (want1, want2)
        assert cut_toks is not None, "replica_kill never fired mid-stream"
        # prefix: what the dead replica delivered under a1
        assert cut_toks[:4] == want1[:4]
        # suffix: the survivor's greedy CONTINUATION of the client's
        # prefix under a2 — not the survivor's own from-scratch decode
        want_suffix = p2.predict(
            {"tokens": prompt + cut_toks[:4], "max_new_tokens": 8}
        )["generated_tokens"]
        assert cut_toks[4:] == want_suffix
        assert cut_toks != want1 and cut_toks != want2
        # client-facing indices stay contiguous across the re-issue and
        # the done event carries the WHOLE delivered stream
        idxs = [e["index"] for e in cut_events if "token" in e]
        assert idxs == list(range(12))
        done_ev = [e for e in cut_events if e.get("done")][-1]
        assert done_ev["generated_tokens"] == cut_toks
        snap = _mx.snapshot()["counters"]
        assert snap.get("serving.stream_replay_divergences") == 1
        assert snap.get("serving.stream_continuations") == 1
    finally:
        gw.stop()
        doomed.stop()
        survivor.stop()


# ----------------------------------------------------------- satellites
def test_chaos_replica_kill_spec():
    from fedml_tpu.comm.chaos import FaultSpec

    spec = FaultSpec.from_dict({"replica_kill": {"1": 5}})
    assert spec.replica_kill == {1: 5}           # keys normalized to int
    assert not spec.replica_killed(1, 4)
    assert spec.replica_killed(1, 5)
    assert not spec.replica_killed(0, 99)        # unscheduled rank
    assert not spec.any_link_faults()            # not a link fault
    with pytest.raises(ValueError, match="replica_kill"):
        FaultSpec(replica_kill={0: -1})
    with pytest.raises(ValueError, match="replica_kill"):
        FaultSpec(replica_kill=[3])


def test_chaos_replica_kill_counts_once_under_concurrent_streams():
    """Two streams on the doomed replica both tick past the threshold
    (the soak's `fed.chaos.replica_kills == 1` pin once read 2): every
    one of them is severed, but ONE kill is executed and counted."""
    from fedml_tpu.comm.chaos import FaultSpec

    class _Pred:
        def predict(self, input_json):
            return {}

    runner = FedMLInferenceRunner(
        _Pred(), port=0, chaos=FaultSpec(replica_kill={0: 2}), chaos_rank=0)
    try:
        c0 = _mx.snapshot()["counters"].get("fed.chaos.replica_kills", 0)
        runner._chaos_tick()                      # token 1: below threshold
        for _ in range(3):                        # tokens 2, 3, 4: all due
            with pytest.raises(ConnectionError, match="killed"):
                runner._chaos_tick()
        assert runner._killed
        c1 = _mx.snapshot()["counters"].get("fed.chaos.replica_kills", 0)
        assert c1 - c0 == 1
    finally:
        runner.stop()


def test_fleet_serve_knob_validation_and_mapping():
    from fedml_tpu.config import Config
    from fedml_tpu.serving.scheduler import fleet_knobs

    cfg = Config.from_dict({"serve": {
        "decode_slots": 2, "drain_timeout_s": 5, "shed_watermark": 2.5,
        "retry_after_s": 2, "probation_deadline_s": 8,
        "probe_backoff_s": 0.1}})
    dep_kw, gw_kw = fleet_knobs(cfg.serve_args.extra)
    assert dep_kw == {"probation_deadline_s": 8.0, "probe_backoff_s": 0.1}
    assert gw_kw == {"shed_watermark": 2.5, "retry_after_s": 2.0}
    for bad in ({"drain_timeout_s": -1}, {"shed_watermark": "x"},
                {"retry_after_s": 0}, {"probation_deadline_s": True},
                {"probe_backoff_s": -0.5}):
        with pytest.raises(ValueError, match="serve_args"):
            Config.from_dict({"serve_args": bad})
    # drain_timeout_s rides the ONE predictor knob mapping
    from fedml_tpu.serving.predictor import lm_predictor_from_serve_knobs

    class _M:    # enough of a model for the recompute path
        attn_fn = None
        n_layers, n_heads, d_model, vocab_size = 1, 2, 32, 64

        def apply(self, *a, **k):
            raise NotImplementedError

    pred = lm_predictor_from_serve_knobs(
        {"drain_timeout_s": 7, "kv_cache": False}, _M(), {})
    assert pred.drain_timeout_s == 7.0
    # the knobs must reach a LIVE fleet, not just the mapping: api's
    # gateway constructor is the production consumer (a validated YAML
    # knob that no code path applies is an inert knob)
    from fedml_tpu import api
    from fedml_tpu.serving.scheduler import Deployment

    gw = api.model_gateway(Deployment.adopt([]), cfg)
    try:
        assert gw.shed_watermark == 2.5 and gw.retry_after_s == 2.0
        # explicit kwargs override the config
        gw2 = api.model_gateway(Deployment.adopt([]), cfg,
                                shed_watermark=9.0)
        try:
            assert gw2.shed_watermark == 9.0
        finally:
            gw2.stop()
    finally:
        gw.stop()


def test_top_renders_fleet_line():
    from fedml_tpu.__main__ import _top_frame
    from fedml_tpu.utils.prometheus import parse_prometheus, \
        render_prometheus

    _mx.inc("serving.requests")
    _mx.inc("serving.shed_total", 3)
    _mx.inc("serving.replica_recoveries")
    _mx.inc("serving.stream_failovers", 2)
    _mx.set_gauge("serving.replicas_ready", 2)
    _mx.set_gauge("serving.replicas_suspect", 1)
    _mx.set_gauge("serving.fleet_version", 4)
    _mx.observe("serving.stream_ttft", 0.012)
    snap = parse_prometheus(render_prometheus(_mx.snapshot()))
    text = _top_frame(snap, "test")
    fleet_lines = [ln for ln in text.splitlines()
                   if ln.startswith("fleet:")]
    assert len(fleet_lines) == 1, text
    line = fleet_lines[0]
    assert "ready 2" in line and "suspect 1" in line
    assert "version 4" in line and "shed 3" in line
    assert "recovered 1" in line and "stream_failovers 2" in line
    assert "stream_ttft_p50<=" in line


def test_fleet_diagnosis_probe_only():
    """The required fleet probe is --only compatible and passes here
    (the full battery exercises it in test_cli_platform)."""
    from fedml_tpu import api

    out = api.fedml_diagnosis(only=["fleet_rolling_update_smoke"])
    chk = out["checks"]["fleet_rolling_update_smoke"]
    assert out["ok"] and chk["ok"], chk
    assert chk["non_2xx"] == 0
    assert set(chk["versions"].values()) == {2}
