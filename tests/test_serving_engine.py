"""Continuous-batching decode engine (serving/engine.py, ISSUE 5).

The two contracts the engine lives by:
- equivalence: greedy engine output is TOKEN-IDENTICAL to the per-request
  path for the same prompts (the slot axis is data-parallel through the
  decode math);
- bounded programs: one step program + one admit program per prompt
  bucket, no matter how many requests stream through (retrace guard).

Plus: mid-flight admission/retirement over fewer slots than requests,
device-side eos retirement, seeded sampling, the predictor route +
fallbacks, HTTP concurrency through FedMLInferenceRunner, and the
serving.ttft / serving.tbt / serving.slots_active / serving.tokens_total
telemetry contract.
"""
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.utils import metrics as _mx

V, D, L, H, FF = 96, 64, 2, 4, 128
MAXLEN = 32


def _setup(seed=0):
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    params = model.init(jax.random.key(seed),
                        jnp.zeros((1, 10), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def setup():
    return _setup()


@pytest.fixture(scope="module")
def per_req(setup):
    """Shared per-request reference predictor — its compiled programs
    (the `want` side of every equivalence pin below) are reused across
    the module instead of recompiling per test."""
    model, params = setup
    return GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True)


@pytest.fixture(scope="module")
def eng_shared(setup):
    """Shared 2-slot default engine for the tests that don't need a
    bespoke knob (eos/fetch_chunk/slot-count pins build their own). The
    conftest swaps a fresh metrics registry per test, so counter
    assertions on the shared engine stay per-test."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN).start()
    yield eng
    eng.stop()


def _prompts(ns, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, V, n).tolist() for n in ns]


# ----------------------------------------------------------- equivalence
def test_engine_greedy_token_identical_to_per_request_path(per_req,
                                                           eng_shared):
    """PINNED equivalence: 5 prompts of different lengths and different
    token budgets through 2 slots — requests are admitted mid-flight as
    earlier ones retire at different steps, and every output must equal
    the per-request path's, token for token."""
    prompts = _prompts((6, 10, 8, 5, 7))
    budgets = [4, 7, 5, 6, 3]
    want = [per_req.predict({"tokens": p, "max_new_tokens": b})
            ["generated_tokens"] for p, b in zip(prompts, budgets)]
    tickets = [eng_shared.submit(p, b) for p, b in zip(prompts, budgets)]
    got = [t.result(timeout=120) for t in tickets]
    assert got == want


def test_engine_program_set_bounded_retrace_guard(setup):
    """One step program total; one admit program per prompt bucket. A
    second wave of requests (same buckets, new temperatures/seeds — all
    traced) must not add a single compile."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN).start()
    try:
        prompts = _prompts((6, 10, 3, 12))   # buckets 8, 16, 4, 16
        for t in [eng.submit(p, 4) for p in prompts]:
            t.result(timeout=120)
        counts = eng.program_counts()
        assert counts["step"] == 1, counts
        assert counts["admit"] == 3, counts   # buckets {4, 8, 16}
        # second wave: same buckets, sampling on, fresh seeds
        for t in [eng.submit(p, 5, temperature=1.3, seed=i)
                  for i, p in enumerate(prompts)]:
            t.result(timeout=120)
        assert eng.program_counts() == counts, "retrace"
    finally:
        eng.stop()


def test_engine_eos_retires_slot_early(setup, per_req):
    model, params = setup
    prompt = _prompts((8,))[0]
    want = per_req.predict({"tokens": prompt, "max_new_tokens": 8})
    want = want["generated_tokens"]
    eos = want[2]
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       eos_id=eos).start()
    try:
        got = eng.submit(prompt, 8).result(timeout=120)
    finally:
        eng.stop()
    # generation stops AT the first eos (inclusive); earlier occurrences
    # of the same value would stop earlier, so compare to the prefix
    assert got == want[:want.index(eos) + 1]


def test_engine_single_token_and_capacity_contract(per_req, eng_shared):
    prompt = _prompts((9,))[0]
    want = per_req.predict({"tokens": prompt, "max_new_tokens": 1})
    # max_new=1: the prefill's token is the whole answer (no steps)
    assert eng_shared.submit(prompt, 1).result(timeout=120) == \
        want["generated_tokens"]
    # exact capacity: prompt + max_new == max_len is admitted...
    ok = eng_shared.submit(prompt, MAXLEN - len(prompt))
    assert len(ok.result(timeout=120)) == MAXLEN - len(prompt)
    # ...one more is refused loudly (no step bucketing in the contract)
    with pytest.raises(ValueError, match=f"cap max_len {MAXLEN}"):
        eng_shared.submit(prompt, MAXLEN - len(prompt) + 1)
    with pytest.raises(ValueError, match="at least one prompt token"):
        eng_shared.submit([], 4)


def test_engine_sampling_seeded(eng_shared):
    """Same seed -> same tokens; different seeds at high temperature
    diverge; greedy slots and sampling slots coexist in the same steps."""
    prompt = _prompts((8,))[0]
    greedy = eng_shared.submit(prompt, 8).result(timeout=120)
    a = eng_shared.submit(prompt, 8, temperature=3.0, seed=7)
    b = eng_shared.submit(prompt, 8, temperature=3.0, seed=7)
    c = eng_shared.submit(prompt, 8, temperature=3.0, seed=8)
    a, b, c = (t.result(timeout=120) for t in (a, b, c))
    assert a == b
    assert a != c
    # and greedy again, mid-sampling-load, still the pinned sequence
    assert eng_shared.submit(prompt, 8).result(timeout=120) == greedy


def test_engine_serves_qlora_layout():
    """int8 frozen base + LoRA adapters (the QLoRA serving layout) through
    the engine: token-identical to the per-request kv path on the same
    quantized tree. (Prompts share one bucket — the layout is what's
    under test here; bucket diversity is pinned above.)"""
    from fedml_tpu.llm.lora import lora_init
    from fedml_tpu.llm.quant import quantize_tree_int8

    model, params = _setup()
    ads = lora_init(jax.random.key(1), params, rank=4, a_std=0.3)
    ads = jax.tree.map(lambda a: a + 0.05 * jnp.ones_like(a), ads)
    qparams = quantize_tree_int8(params)
    prompts = _prompts((7, 6, 5))
    per_req = GreedyLMPredictor(model, qparams, max_len=MAXLEN,
                                kv_cache=True, adapters=ads)
    want = [per_req.predict({"tokens": p, "max_new_tokens": 5})
            ["generated_tokens"] for p in prompts]
    eng = DecodeEngine(model, qparams, adapters=ads, n_slots=2,
                       max_len=MAXLEN).start()
    try:
        got = [t.result(timeout=120)
               for t in [eng.submit(p, 5) for p in prompts]]
    finally:
        eng.stop()
    assert got == want


# ------------------------------------------------------ predictor routing
def test_predictor_engine_route_and_fallbacks(setup, per_req):
    model, params = setup
    prompt = _prompts((9,))[0]
    plain = per_req
    eng = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                            decode_slots=2)
    try:
        req = {"tokens": prompt, "max_new_tokens": 6}
        assert eng.predict(req) == plain.predict(req)
        # engine-routed requests are visible in the engine counters
        assert _mx.snapshot()["counters"]["serving.engine.requests"] >= 1
        # batched rows and top_k requests FALL BACK to the per-request path
        before = _mx.snapshot()["counters"]["serving.engine.requests"]
        batched = eng.predict({"tokens": [prompt, prompt[:4]],
                               "max_new_tokens": 3})
        assert len(batched["generated_tokens"]) == 2
        topk = eng.predict({"tokens": prompt, "max_new_tokens": 3,
                            "temperature": 1.0, "top_k": 4, "seed": 1})
        assert topk["generated_tokens"] == plain.predict(
            {"tokens": prompt, "max_new_tokens": 3, "temperature": 1.0,
             "top_k": 4, "seed": 1})["generated_tokens"]
        assert _mx.snapshot()["counters"][
            "serving.engine.requests"] == before
        # engine capacity is EXACT: a request the per-request path would
        # refuse (prompt + bucketed steps > max_len) is served when
        # prompt + max_new fits
        tight = {"tokens": prompt, "max_new_tokens": MAXLEN - len(prompt)}
        with pytest.raises(ValueError, match="bucketed"):
            plain.predict(tight)
        assert len(eng.predict(tight)["generated_tokens"]) == \
            MAXLEN - len(prompt)
        # decode_slots without kv_cache refuses loudly
        with pytest.raises(ValueError, match="needs kv_cache=True"):
            GreedyLMPredictor(model, params, max_len=MAXLEN,
                              decode_slots=2)
    finally:
        eng.stop()


def test_engine_hostile_seed_and_dead_engine_fallback(setup):
    """Review hardening: (a) an out-of-uint32-range client seed must not
    crash the engine thread (it is masked, still deterministic); (b) after
    the engine stops, routed requests degrade to the per-request path
    instead of queueing into a dead loop."""
    model, params = setup
    prompt = _prompts((7,))[0]
    pred = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=2)
    try:
        req = {"tokens": prompt, "max_new_tokens": 4, "temperature": 2.0}
        a = pred.predict({**req, "seed": -1})
        b = pred.predict({**req, "seed": -1})
        assert a == b                       # masked, deterministic
        huge = pred.predict({**req, "seed": 2 ** 40 + 3})
        assert len(huge["generated_tokens"]) == 4
        # engine still alive and greedy-consistent after the hostile seeds
        want = pred.predict({"tokens": prompt, "max_new_tokens": 4})
    finally:
        pred.stop()
    # dead engine: the route falls back per-request, same greedy tokens
    got = pred.predict({"tokens": prompt, "max_new_tokens": 4})
    assert got["generated_tokens"] == want["generated_tokens"]
    # unseeded sampling also degrades (no reproducibility contract)...
    assert len(pred.predict({"tokens": prompt, "max_new_tokens": 4,
                             "temperature": 1.0})["generated_tokens"]) == 4
    # ...but SEEDED sampling surfaces the failure: the per-request rng
    # schedule differs from the engine's, so a silent degrade would break
    # same-seed-same-tokens with no signal
    with pytest.raises(RuntimeError, match="stopped"):
        pred.predict({"tokens": prompt, "max_new_tokens": 4,
                      "temperature": 1.0, "seed": 7})
    # ...and so does a request only the ENGINE's capacity contract admits
    # (prompt + bucketed steps > max_len would 400 on the per-request
    # path — a misleading client error for a previously-valid request)
    with pytest.raises(RuntimeError, match="stopped"):
        pred.predict({"tokens": prompt,
                      "max_new_tokens": MAXLEN - len(prompt)})
    with pytest.raises(RuntimeError, match="stopped"):
        pred.engine.submit(prompt, 2)
    # an eos-configured predictor never degrades silently either (the
    # per-request path would emit post-eos tokens)
    eosp = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=2, eos_id=1)
    eosp.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        eosp.predict({"tokens": prompt, "max_new_tokens": 4})


def test_engine_telemetry_contract(eng_shared):
    """serving.ttft/tbt histograms, serving.tokens_total counter,
    serving.slots_active gauge, and engine spans on the recorder (the
    conftest's per-test registry/recorder swap keeps the counts exact on
    the shared engine)."""
    from fedml_tpu.utils.events import recorder

    tickets = [eng_shared.submit(p, 6) for p in _prompts((8, 6, 9, 7))]
    outs = [t.result(timeout=120) for t in tickets]
    snap = _mx.snapshot()
    assert snap["counters"]["serving.tokens_total"] == sum(
        len(o) for o in outs) == 24
    assert snap["counters"]["serving.engine.completions"] == 4
    assert snap["histograms"]["serving.ttft"]["count"] == 4
    assert snap["histograms"]["serving.tbt"]["count"] == 4
    # slots_active was set from fetched frames (last frame may be 0; the
    # gauge existing at all proves the plane is wired — concurrency is
    # asserted via HTTP below)
    assert "serving.slots_active" in snap["gauges"]
    spans = {s.name for s in recorder.spans}
    assert "serving.engine.admit" in spans
    assert "serving.engine.fetch" in spans


def test_http_concurrency_through_engine_runner(setup):
    """8 concurrent HTTP requests through FedMLInferenceRunner on an
    engine-backed predictor: every request gets exactly one response,
    some device step advanced more than one request (the engine's drained
    step frames held more live slots than there were frames — counted, not
    caught by polling a gauge at the right two milliseconds), and the
    in-flight gauge returns to zero (atomic counter satellite)."""
    from fedml_tpu.serving.inference_runner import FedMLInferenceRunner

    model, params = setup
    pred = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=4)
    runner = FedMLInferenceRunner(pred, port=0).start()
    url = f"http://127.0.0.1:{runner.port}/predict"
    prompts = _prompts((6, 10, 8, 5, 7, 9, 4, 11), seed=3)
    # long enough outputs, sent at one moment, that a loaded box cannot
    # serialize the requests past each other's whole decode
    new = MAXLEN - max(len(p) for p in prompts)
    go = threading.Barrier(len(prompts))
    want = [pred.predict({"tokens": p, "max_new_tokens": new})
            ["generated_tokens"] for p in prompts]

    def steps():
        c = _mx.snapshot()["counters"]
        return (c.get("serving.engine.steps", 0),
                c.get("serving.engine.slot_steps", 0))

    steps0, slot_steps0 = steps()       # after the serial reference above
    results: list = [None] * len(prompts)

    def hit(i):
        body = json.dumps({"tokens": prompts[i],
                           "max_new_tokens": new}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        go.wait(timeout=60)
        with urllib.request.urlopen(req, timeout=120) as r:
            results[i] = json.loads(r.read())["generated_tokens"]

    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        runner.stop()
    assert results == want
    steps1, slot_steps1 = steps()
    assert slot_steps1 - slot_steps0 > steps1 - steps0 > 0, (
        "requests never shared a device step")
    assert _mx.snapshot()["gauges"]["serving.queue_depth"] == 0


# ------------------------------------------------------------- satellites
def test_sampler_cache_lru_bounded(setup):
    """A diverse stream of top_k values cannot grow the per-top_k jit
    cache without limit: LRU cap + eviction counter."""
    model, params = setup
    pred = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             sampler_cache_size=2)
    prompt = _prompts((6,))[0]
    for tk in (2, 5, 9, 17):   # buckets 2, 8, 16, 32
        pred.predict({"tokens": prompt, "max_new_tokens": 2,
                      "temperature": 1.0, "top_k": tk, "seed": 1})
    assert len(pred._samplers) == 2
    assert list(pred._samplers) == [16, 32]   # LRU order, oldest evicted
    assert _mx.snapshot()["counters"]["serving.sampler_evictions"] == 2
    # re-requesting an evicted bucket rebuilds it (and evicts again)
    pred.predict({"tokens": prompt, "max_new_tokens": 2,
                  "temperature": 1.0, "top_k": 2, "seed": 1})
    assert list(pred._samplers) == [32, 2]


def test_atomic_counter():
    c = _mx.AtomicCounter()
    errs = []

    def bump():
        try:
            for _ in range(2000):
                c.inc()
                c.dec()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert c.value() == 0
    assert c.inc(3) == 3 and c.dec() == 2


def test_serve_args_config_validation():
    from fedml_tpu.config import Config

    cfg = Config.from_dict({"serve": {"decode_slots": 4,
                                      "engine_max_len": 128}})
    assert cfg.serve_args.extra["decode_slots"] == 4
    for bad in ({"decode_slots": -1}, {"decode_slots": True},
                {"engine_max_len": 0}, {"engine_fetch_chunk": "x"},
                {"engine_eos_id": -2}):
        with pytest.raises(ValueError, match="serve_args"):
            Config.from_dict({"serve_args": bad})
    # both sections present is ambiguous — refused, not silently dropped
    with pytest.raises(ValueError, match="both 'serve' and 'serve_args'"):
        Config.from_dict({"serve": {"decode_slots": 8}, "serve_args": {}})
    # a MISSPELLED knob must fail loudly, not bring the replica up in
    # per-request mode silently
    with pytest.raises(ValueError, match="unknown serve_args knob"):
        Config.from_dict({"serve": {"decode_slot": 8}})
    with pytest.raises(ValueError, match="kv_cache must be a boolean"):
        Config.from_dict({"serve": {"kv_cache": "yes"}})
    assert Config.from_dict(
        {"serve": {"kv_cache": False}}).serve_args.extra["kv_cache"] is False


def test_lm_predictor_from_config_consumes_serve_args(setup, per_req):
    """cfg.serve_args is actually consumed (not just validated): the
    config bridge builds an engine-backed predictor from YAML knobs."""
    from fedml_tpu.config import Config
    from fedml_tpu.serving import lm_predictor_from_config

    model, params = setup
    cfg = Config.from_dict({"serve": {"decode_slots": 2,
                                      "engine_max_len": MAXLEN,
                                      "engine_fetch_chunk": 3,
                                      "sampler_cache_size": 2}})
    pred = lm_predictor_from_config(cfg, model, params)
    try:
        assert pred.engine is not None
        assert pred.engine.n_slots == 2
        assert pred.engine.fetch_chunk == 3
        assert pred._samplers_cap == 2
        prompt = _prompts((7,))[0]
        want = per_req.predict({"tokens": prompt, "max_new_tokens": 4})
        assert pred.predict({"tokens": prompt, "max_new_tokens": 4}) == want
    finally:
        pred.stop()
    # decode_slots omitted -> plain per-request predictor
    plain = lm_predictor_from_config(Config.from_dict({}), model, params)
    assert plain.engine is None


def test_slots_active_gauge_returns_to_zero_fetch_chunk_1(setup):
    """Regression: with fetch_chunk=1 the final completing frame's ENTRY
    mask is nonzero and no trailing all-inactive frame is dispatched — a
    gauge published from entry masks would read busy forever at idle."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       fetch_chunk=1).start()
    try:
        for t in [eng.submit(p, 5) for p in _prompts((6, 8, 7))]:
            t.result(timeout=120)
        deadline = time.monotonic() + 10
        g = _mx.registry.gauge("serving.slots_active")
        while g.value() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert g.value() == 0
    finally:
        eng.stop()


def test_runner_maps_server_errors_to_500():
    """Only the dedicated InvalidRequest (and missing-field KeyError) map
    to 400; every other exception — including a plain ValueError, the
    shape internal JAX errors surface as — is a 500, so the gateway's
    4xx/5xx split fails a broken replica over instead of keeping it in
    rotation behind a misleading client error."""
    import urllib.error

    from fedml_tpu.serving.inference_runner import FedMLInferenceRunner
    from fedml_tpu.serving.predictor import InvalidRequest

    class Boom:
        def predict(self, j):
            if j.get("bad_input"):
                raise InvalidRequest("bad input")
            if j.get("internal_valueerror"):
                raise ValueError("jax shape mismatch")   # internal class
            raise RuntimeError("engine died")

    runner = FedMLInferenceRunner(Boom(), port=0).start()
    url = f"http://127.0.0.1:{runner.port}/predict"
    try:
        for payload, code in (({"bad_input": 1}, 400),
                              ({"internal_valueerror": 1}, 500),
                              ({}, 500)):
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == code
        # real predictor validation errors ride InvalidRequest -> 400
        # (e.g. non-integer tokens — hostile input must NOT 500, or the
        # gateway would let clients kill replicas on demand)
    finally:
        runner.stop()


def test_start_replica_lm_spec_with_engine(tmp_path, setup, per_req):
    """Deploy-path wiring: a serve spec with model_kind=lm and
    serve.decode_slots brings up an engine-backed LM replica whose
    /predict matches the per-request path."""
    from fedml_tpu.serving.scheduler import start_replica

    model, params = setup
    prompt = _prompts((7,))[0]
    want = per_req.predict({"tokens": prompt, "max_new_tokens": 5})
    spec = {"model_kind": "lm",
            "lm": {"vocab_size": V, "d_model": D, "n_layers": L,
                   "n_heads": H, "d_ff": FF, "scan_layers": True},
            "serve": {"decode_slots": 2, "engine_max_len": MAXLEN},
            "params": params, "port": 0}
    rid, runner = start_replica(spec)
    try:
        assert runner.predictor.engine is not None
        body = json.dumps({"tokens": prompt, "max_new_tokens": 5}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{runner.port}/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert out["generated_tokens"] == want["generated_tokens"]
    finally:
        runner.stop()
    # runner.stop() also stopped the engine thread
    assert runner.predictor.engine._stopping
