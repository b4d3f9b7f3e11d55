"""The decode engine's loop names what it is doing (serving/engine.py `_loop`):
`serving.engine.wait` only while nothing is live, queued or in flight;
`.plan` once for every admission it starts; `.dispatch` around every program
call, the frame-leaving ones numbered; `.deliver` once for every drained
frame, under its dispatch's number. On tiny CPU engines of each loop form
(plain steps with chunked prefill, batched admission, n-gram speculation,
block diffusion); every request is submitted while the test holds the
engine's lock, so the loop sees them together. No assertion depends on a
duration."""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.utils.events import recorder

WAIT, PLAN, DISPATCH = ("serving.engine.wait", "serving.engine.plan",
                        "serving.engine.dispatch")
FETCH, DELIVER, ADMIT = ("serving.engine.fetch", "serving.engine.deliver",
                         "serving.engine.admit")
WORK = (PLAN, DISPATCH, FETCH, DELIVER)


def lm_engine(**kw):
    model = TransformerLM(vocab_size=96, d_model=32, n_layers=2, n_heads=4,
                          d_ff=64, scan_layers=True)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return DecodeEngine(model, params, n_slots=2, max_len=32, page_size=4,
                        prefill_chunk=4, **kw), 96


def block_engine():
    from chipbench import inputs, manifest

    cfg = manifest.load_json(manifest.HERE / "configs"
                             / "sdar_30b_a3b_pp8.json")
    model = {**cfg["model"], **cfg["rehearse"]["model"]}
    lm, _spec = manifest.find("models", "sdar_moe")(model)
    params = inputs.init_tree(inputs.param_shapes(lm), 3, 1.0, "float32")
    return DecodeEngine(lm, params, n_slots=2, max_len=64, page_size=4,
                        prefill_chunk=8), model["mask_token_id"]


FORMS = {"step": lambda: lm_engine(),
         "admit_batch": lambda: lm_engine(admit_batch=2, fetch_chunk=1),
         "spec": lambda: lm_engine(spec_decode="ngram", spec_k=3),
         "block": block_engine}


@pytest.fixture(scope="module", params=sorted(FORMS))
def served(request):
    """(form, prompts, the engine's spans from its start to its stop) of
    three requests through two slots, the engine's first idle turn after
    the last token included."""
    eng, vocab = FORMS[request.param]()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, vocab, n).tolist() for n in (9, 6, 11)]
    since = time.perf_counter()
    eng.start()
    try:
        with eng._cond:
            tickets = [eng.submit(p, 8) for p in prompts]
        for t in tickets:
            t.result(timeout=600)
        done = time.perf_counter()
        deadline = time.monotonic() + 120
        while not any(s.name == WAIT and s.start >= done
                      for s in list(recorder.spans)):
            assert time.monotonic() < deadline, "the loop never went idle"
            time.sleep(0.02)
    finally:
        eng.stop()
    spans = [s for s in list(recorder.spans) if s.start >= since
             and s.name.startswith("serving.engine.")]
    return request.param, prompts, spans


def named(spans, *names):
    return [s for s in spans if s.name in names]


def test_a_wait_never_overlaps_the_loops_work(served):
    _form, _prompts, spans = served
    waits, work = named(spans, WAIT), named(spans, *WORK)
    assert waits and work
    for w in waits:
        for o in work:
            assert w.end <= o.start or o.end <= w.start, (w, o)


def test_every_drained_frame_has_one_dispatch_and_one_deliver(served):
    form, _prompts, spans = served
    numbered = {s.meta["frame"]: s for s in named(spans, DISPATCH)
                if "frame" in s.meta}
    dispatched = collections.Counter(
        s.meta["frame"] for s in named(spans, DISPATCH) if "frame" in s.meta)
    delivered = collections.Counter(
        s.meta["frame"] for s in named(spans, DELIVER))
    assert dispatched == delivered
    assert set(dispatched.values()) == {1}
    assert sorted(dispatched) == list(range(1, len(dispatched) + 1))
    for s in named(spans, DELIVER):
        assert s.start >= numbered[s.meta["frame"]].end
    programs = collections.Counter(s.meta["program"]
                                   for s in named(spans, DISPATCH))
    iterate = {"block": "block", "spec": "spec"}.get(form, "step")
    assert set(programs) == {"admit", iterate}
    # a frame for every forward, step or window; the admissions' first
    # tokens ride frames of their own, except a diffusion model's
    by_program = collections.Counter(
        s.meta["program"] for s in numbered.values())
    assert by_program[iterate] == programs[iterate]
    if form == "block":
        assert by_program["admit"] == 0
    else:
        assert 1 <= by_program["admit"] <= 3 < programs["admit"]


def test_every_chunk_dispatch_lies_inside_its_admit_span(served):
    _form, _prompts, spans = served
    admits = named(spans, ADMIT)
    chunks = [s for s in named(spans, DISPATCH)
              if s.meta["program"] == "admit"]
    assert len(admits) == len(chunks)
    for a, d in zip(admits, chunks):
        assert a.start <= d.start and d.end <= a.end


def test_one_plan_per_admission(served):
    _form, prompts, spans = served
    plans = named(spans, PLAN)
    assert sorted(s.meta["prompt"] for s in plans) == sorted(
        len(p) for p in prompts)
    assert all(s.meta["hit_pages"] >= 0 and "pinned" not in s.meta
               for s in plans)


def test_a_wait_comes_only_while_the_engine_is_idle(served):
    _form, _prompts, spans = served
    work = named(spans, *WORK)
    first, last = min(s.start for s in work), max(s.end for s in work)
    waits = named(spans, WAIT)
    assert all(w.end <= first or w.start >= last for w in waits)
    assert any(w.start >= last for w in waits)
