"""A block-diffusion expert model through the engine's NORMAL path
(`start_replica` -> `DecodeEngine` -> HTTP `/predict`, streamed): the recipe's
new fields, the request's two parameters and the stream's two notes, the
completions counted by the engine and none by a fallback; and what is
refused, each by its name: the model with `spec_decode`, `kv_quant: int8`,
`admit_batch` > 1 or a mesh, a page or chunk that cuts a block, the
per-request fallback, the request's parameters on a model that has no block,
and what the sequence-parallel round still does not train."""
import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import sdar_30b_a3b_pp8 as ref
from fedml_tpu.llm import decode
from fedml_tpu.llm.moe import MoE
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.serving.scheduler import start_replica
from fedml_tpu.utils import metrics as mx

CFG = manifest.load_json(manifest.HERE / "configs" / "sdar_30b_a3b_pp8.json")
MODEL = {**CFG["model"], **CFG["rehearse"]["model"]}
SERVE = {"decode_slots": 2, "engine_max_len": 64, "kv_page_size": 4,
         "prefill_chunk": 8}


@pytest.fixture(scope="module")
def built():
    lm, spec = manifest.find("models", "sdar_moe")(MODEL)
    params = inputs.init_tree(inputs.param_shapes(lm), 3, 1.0, "float32")
    return lm, spec, params


@pytest.fixture(scope="module")
def replica(built):
    _lm, spec, params = built
    _job, runner = start_replica(
        {**spec, "params": params, "port": 0, "serve": dict(SERVE)})
    yield runner
    runner.stop()


def post(port: int, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    conn.request("POST", "/predict", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def events(data: bytes) -> list:
    return [json.loads(line[5:]) for line in data.split(b"\n")
            if line.startswith(b"data:")]


def test_the_recipe_builds_the_module_the_builder_made(built, replica):
    lm, spec, _params = built
    assert {"n_kv_heads", "head_dim", "qk_norm", "diffusion_block",
            "mask_id"} <= set(spec["lm"])
    assert spec["lm"]["moe"]["scoring"] == "softmax"
    assert spec["lm"]["moe"]["n_shared"] == 0
    served = replica.predictor.engine.model
    assert served == lm and served.diffusion_block == 4
    assert decode.unserved(lm) == []
    assert decode.engine_only(lm) == "generation by diffusion over blocks"
    with pytest.raises(NotImplementedError, match="window layers and layers "
                       "without rotary positions cannot be served yet"):
        start_replica({"model_kind": "lm", "lm": {
            **spec["lm"], "window": 8}, "params": {}})


def test_the_stream_carries_each_tokens_forward_and_confidence(built,
                                                               replica):
    _lm, _spec, params = built
    toks = [int(v) for v in np.random.RandomState(4).randint(1, 127, 10)]
    before = mx.snapshot()["counters"]
    status, data = post(replica.port, {
        "tokens": toks, "max_new_tokens": 10, "stream": True,
        "denoising_steps": 2, "confidence_threshold": None})
    assert status == 200
    evs = events(data)
    tokens = [e for e in evs if "token" in e]
    want, notes = ref.generate(params, toks, 10, MODEL, 2, None)
    assert [e["token"] for e in tokens] == want
    assert [e["index"] for e in tokens] == list(range(10))
    assert [e["forward"] for e in tokens] == [f for f, _ in notes]
    assert max(abs(e["confidence"] - c)
               for e, (_, c) in zip(tokens, notes)) < 2e-5
    assert evs[-1] == {"done": True, "generated_tokens": want}
    after = mx.snapshot()["counters"]
    assert after["serving.engine.completions"] - before.get(
        "serving.engine.completions", 0) == 1


def test_the_requests_defaults_are_the_families(built, replica):
    """No parameter named: 4 steps (the block length) and the threshold 0.9,
    the dynamic rule; a plain /predict answers what the stream does."""
    _lm, _spec, params = built
    toks = [int(v) for v in np.random.RandomState(5).randint(1, 127, 7)]
    status, data = post(replica.port, {"tokens": toks, "max_new_tokens": 6})
    assert status == 200
    want, _ = ref.generate(params, toks, 6, MODEL, 4, 0.9)
    assert json.loads(data)["generated_tokens"] == want
    status, data = post(replica.port, {
        "tokens": toks, "max_new_tokens": 6, "denoising_steps": 9})
    assert status == 400 and b"denoising_steps must be 1 .. 4" in data
    status, data = post(replica.port, {
        "tokens": toks, "max_new_tokens": 6, "top_k": 3, "temperature": 1.0})
    assert status == 400 and b"decode engine only" in data


def test_a_model_without_a_block_refuses_the_parameters_with_a_sentence():
    lm = TransformerLM(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                       d_ff=32)
    params = lm.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32))["params"]
    pred = GreedyLMPredictor(lm, params, max_len=32, kv_cache=True,
                             decode_slots=1)
    try:
        with pytest.raises(ValueError, match="block-diffusion model's "
                           "parameters; this model generates one token"):
            pred.predict({"tokens": [1, 2], "max_new_tokens": 2,
                          "denoising_steps": 2})
        with pytest.raises(ValueError, match="block-diffusion model's"):
            pred.engine.submit([1, 2], 2, confidence_threshold=0.5)
        assert pred.predict({"tokens": [1, 2], "max_new_tokens": 2})[
            "generated_tokens"]
    finally:
        pred.stop()
    plain = GreedyLMPredictor(lm, params, max_len=32, kv_cache=True)
    with pytest.raises(ValueError, match="confidence_threshold: a "
                       "block-diffusion model's"):
        plain.predict({"tokens": [1, 2], "max_new_tokens": 2,
                       "confidence_threshold": None})


@pytest.mark.parametrize("knob, kw", [
    ("spec_decode", {"spec_decode": "ngram"}),
    ("kv_quant='int8'", {"kv_quant": "int8"}),
    ("admit_batch > 1", {"admit_batch": 2}),
])
def test_the_engine_refuses_by_name_what_a_block_forward_has_not(built, knob,
                                                                 kw):
    lm, _spec, params = built
    with pytest.raises(NotImplementedError,
                       match=f"{knob} with a diffusion model"):
        DecodeEngine(lm, params, n_slots=1, max_len=32, page_size=4, **kw)


def test_a_mesh_and_a_cut_block_are_refused(built):
    from fedml_tpu.parallel.mesh import make_mesh

    lm, _spec, params = built
    with pytest.raises(NotImplementedError, match="a mesh .mp > 1. with a "
                       "diffusion model"):
        DecodeEngine(lm, params, n_slots=1, max_len=32, page_size=4,
                     mesh=make_mesh({"mp": 2}))
    for kw, name in (({"page_size": 6}, "kv_page_size 6"),
                     ({"page_size": 4, "prefill_chunk": 6},
                      "prefill_chunk 6"),
                     ({"page_size": 2, "max_len": 30}, "kv_page_size 2")):
        with pytest.raises(ValueError, match=f"{name} is not a whole number "
                           "of the model's diffusion blocks of 4"):
            DecodeEngine(lm, params, **{"n_slots": 1, "max_len": 32, **kw})


def test_the_per_request_fallback_is_refused_by_what_it_lacks(built):
    lm, _spec, params = built
    with pytest.raises(NotImplementedError, match="generation by diffusion "
                       "over blocks: served by the decode engine only"):
        GreedyLMPredictor(lm, params, kv_cache=True)
    gqa = TransformerLM(vocab_size=8, d_model=32, n_heads=4, n_kv_heads=2)
    with pytest.raises(NotImplementedError, match="grouped KV heads, per-head"
                       " q/k norms or experts: served by the decode engine"):
        GreedyLMPredictor(gqa, {}, kv_cache=True)
    assert decode.engine_only(TransformerLM(vocab_size=8, d_model=32,
                                            n_heads=4)) == ""


def test_grouped_heads_and_experts_are_served_a_token_a_step():
    """The causal half of what the model forced: 4 heads over 2 KV heads of
    their own width with q/k norms and a softmax expert layer, a token a
    step through `_step_all`, gather and kernel alike, against the module's
    whole forward."""
    lm = TransformerLM(
        vocab_size=40, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=2, head_dim=16, qk_norm=True, norm_eps=1e-5,
        rope_base=5e5, moe=MoE(n_experts=4, top_k=2, d_expert=16, n_shared=0,
                               scoring="softmax"),
        layer_kinds=(("full", "dense"), ("full", "moe")))
    toks = [int(v) for v in np.random.RandomState(0).randint(1, 40, 9)]
    params = lm.init(jax.random.key(0), jnp.asarray([toks]))["params"]
    seq = list(toks)
    whole = jax.jit(lambda ids: lm.apply({"params": params}, ids))
    for _ in range(6):                  # causal: the padding changes nothing
        ids = jnp.asarray([seq + [0] * (16 - len(seq))])
        seq.append(int(jnp.argmax(whole(ids)[0, len(seq) - 1])))
    for kernel in (False, True):
        eng = DecodeEngine(lm, params, n_slots=2, max_len=32, page_size=4,
                           prefill_chunk=8, paged_kernel=kernel).start()
        try:
            assert eng.submit(toks, 6).result(timeout=300) == seq[9:]
        finally:
            eng.stop()


def test_the_sequence_parallel_round_keeps_its_own_condition():
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import make_fedllm_seq_round
    from fedml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"silos": 1, "seq": 2})
    for lm, name in (
            (TransformerLM(vocab_size=8, d_model=32, n_heads=4,
                           n_kv_heads=2), "grouped KV heads"),
            (TransformerLM(vocab_size=8, d_model=32, n_heads=4,
                           qk_norm=True), "per-head q/k norms"),
            (TransformerLM(vocab_size=8, d_model=32, n_layers=1, n_heads=4,
                           moe=MoE(n_experts=2, top_k=1, d_expert=8),
                           layer_kinds=(("full", "moe"),)), "experts"),
            (TransformerLM(vocab_size=8, d_model=32, n_heads=4,
                           diffusion_block=4, mask_id=7),
             "generation by diffusion over blocks")):
        assert decode.unserved(lm) == []
        with pytest.raises(NotImplementedError, match=name):
            make_fedllm_seq_round(lm, None, TrainArgs(), mesh)
