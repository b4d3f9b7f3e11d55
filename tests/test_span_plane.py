"""The span plane from the program into a device trace (ISSUE 26).

Three sources, one set of names: `jax.named_scope`s inside the round and
decode programs (read off the CPU lowering: metadata only), the request's
life inside the engine as spans of its caller's trace, and the counters a
drained step frame bumps. What reads them on the chip is chipbench's
(tests/chipbench/test_chipbench_spanplane.py); here is what the program
promises.
"""
import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine, submitted_ticket
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.utils import metrics as _mx
from fedml_tpu.utils.events import EventRecorder, recorder, trace_context

V, D, L, H, FF = 64, 32, 2, 2, 64
MAXLEN = 64
SCOPE = re.compile(r"(?:fed|lm|decode)\.\w+")


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, V, n).tolist()


# ------------------------------------------------------------- record_span
def test_record_span_inherits_the_open_span_like_a_with_block():
    rec = EventRecorder(max_rows=8)
    rows = []
    rec.sinks.append(lambda kind, row: rows.append((kind, row)))
    with rec.span("outer") as outer:
        s = rec.record_span("late", 1.0, 3.5, chunks=4)
    assert (s.trace_id, s.parent_id) == (outer.trace_id, outer.span_id)
    assert s.span_id and s.span_id != outer.span_id
    assert s.duration == 2.5 and s.meta == {"chunks": 4}
    assert [r["name"] for _, r in rows] == ["late", "outer"]
    assert rows[0][1]["duration"] == 2.5 and rows[0][1]["chunks"] == 4
    assert rows[0][1]["parent_id"] == outer.span_id
    assert rec.summary()["late"] == {"count": 1, "total_s": 2.5}


def test_record_span_joins_a_given_trace_from_another_thread():
    rec = EventRecorder(max_rows=8)
    a = rec.record_span("x", 0.0, 1.0, trace_id="t" * 16, parent_id="p" * 16)
    assert (a.trace_id, a.parent_id) == ("t" * 16, "p" * 16)
    with trace_context("other", "span"):     # the caller's own is not used
        b = rec.record_span("y", 1.0, 2.0, trace_id="t" * 16)
    assert b.trace_id == "t" * 16 and b.parent_id == ""
    alone = rec.record_span("z", 0.0, 0.5)   # no open span: a fresh trace
    assert alone.trace_id not in ("", "t" * 16) and alone.parent_id == ""


def test_record_span_counts_what_the_ring_evicts():
    rec = EventRecorder(max_rows=2)
    for i in range(5):
        rec.record_span("serving.engine.queue", 0.0, float(i))
    assert len(rec.spans) == 2 and rec.dropped["serving"] == 3
    assert rec.summary()["serving.engine.queue"]["count"] == 5  # exact


# ------------------------------------------------ a request inside the engine
@pytest.fixture(scope="module")
def paged_runner(lm):
    from fedml_tpu.serving.inference_runner import FedMLInferenceRunner

    model, params = lm
    pred = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=4, kv_page_size=8, prefill_chunk=8)
    runner = FedMLInferenceRunner(pred, port=0).start()
    yield runner
    runner.stop()


def _stream(port, prompt, new):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps({"tokens": prompt, "max_new_tokens": new,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        raw = r.read().decode()
    return [json.loads(ln[len("data:"):]) for ln in raw.split("\n\n")
            if ln.strip().startswith("data:")]


FIVE = ("serving.http.in", "serving.engine.queue", "serving.engine.prefill",
        "serving.engine.first_fetch", "serving.http.out")


def test_five_spans_of_one_streamed_request(paged_runner):
    """One trace id under `serving.request`, in order, end to start, and
    the engine's three add up to its own time to first token."""
    n0 = len(recorder.spans)
    events = _stream(paged_runner.port, _prompt(27, seed=1), 5)
    assert len([e for e in events if "token" in e]) == 5
    spans = recorder.spans[n0:]
    req = [s for s in spans if s.name == "serving.request"]
    assert len(req) == 1
    mine = {s.name: s for s in spans
            if s.trace_id == req[0].trace_id and s.name in FIVE}
    assert tuple(sorted(mine, key=FIVE.index)) == FIVE
    assert all(s.parent_id == req[0].span_id for s in mine.values())
    chain = [mine[n] for n in FIVE]
    for a, b in zip(chain, chain[1:]):
        assert a.start <= a.end == b.start      # contiguous, one clock
    queue, prefill, fetch = chain[1:4]
    # 27 tokens in chunks of 8: four chunks, none served by the prefix cache
    assert prefill.meta == {"chunks": 4, "prompt": 27, "hit_pages": 0}
    engine_ttft = fetch.end - queue.start       # t_first - t_submit
    assert sum(s.duration for s in chain[1:4]) == pytest.approx(
        engine_ttft, abs=1e-3)
    ttft = _mx.snapshot()["histograms"]["serving.ttft"]
    assert ttft["count"] == 1 and ttft["sum"] == pytest.approx(
        engine_ttft, abs=1e-6)
    # the handler's two ends lie inside its own request span
    assert req[0].start <= chain[0].start + 1e-3
    assert chain[-1].end <= req[0].end


def test_a_ticket_submitted_outside_any_span_starts_its_own_trace(lm):
    model, params = lm
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN).start()
    try:
        n0 = len(recorder.spans)
        tk = eng.submit(_prompt(6), 3)
        assert tk.result(timeout=120) and tk.trace == (None, None)
        assert submitted_ticket(None) is None
        three = [s for s in recorder.spans[n0:] if s.name in FIVE]
        assert [s.name for s in three] == list(FIVE[1:4])
        assert len({s.trace_id for s in three}) == 1 and three[0].trace_id
        assert three[1].meta["chunks"] == 1     # prefill_chunk 0: one chunk
        assert three[2].end == tk.t_first and three[0].start == tk.t_submit
    finally:
        eng.stop()


def test_slot_steps_count_the_tokens_step_frames_delivered(lm):
    """`serving.engine.steps` counts drained step frames, `slot_steps` the
    live slots in them: every token but a request's first comes from one
    live slot of one step frame, and occupancy cannot pass the slots.
    `page_steps` adds up the pages each of those steps' query attended:
    prompt + the tokens emitted so far, in pages."""
    model, params = lm
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN,
                       page_size=8, prefill_chunk=8).start()
    prompts = (5, 11, 8, 4, 17)
    try:
        tickets = [eng.submit(_prompt(n, seed=n), new)
                   for n, new in zip(prompts, (7, 3, 9, 1, 6))]
        outs = [t.result(timeout=120) for t in tickets]
    finally:
        eng.stop()
    snap = _mx.snapshot()
    c = snap["counters"]
    assert c["serving.engine.page_steps"] == sum(
        -(-(n + emitted) // 8)
        for n, o in zip(prompts, outs) for emitted in range(1, len(o)))
    assert snap["gauges"]["serving.engine.table_pages"] == (
        3 * -(-MAXLEN // 8))
    by_steps = sum(len(o) - 1 for o in outs)
    assert c["serving.engine.slot_steps"] == by_steps > 0
    assert c["serving.engine.steps"] >= max(len(o) - 1 for o in outs)
    assert c["serving.engine.slot_steps"] <= c["serving.engine.steps"] * 3
    assert c["serving.tokens_total"] == by_steps + len(outs)


# --------------------------------------------- named scopes in the programs
def _op_names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _leaves(names) -> set:
    """Each operation's INNERMOST scope (how chipbench reads a path)."""
    return {m[-1] for m in (SCOPE.findall(n) for n in names) if m}


def _round_args(n, x):
    """(data, ids, weights) of `n` clients whose shard `x` is [S, ...]."""
    data = {"x": jnp.broadcast_to(x, (n,) + x.shape),
            "y": jnp.zeros((n,) + x.shape[: 2 if x.dtype == jnp.int32 else 1],
                           jnp.int32),
            "mask": jnp.ones((n, x.shape[0]), jnp.float32)}
    return data, jnp.arange(n), jnp.full((n,), float(x.shape[0]))


def test_round_body_lowering_names_its_layers():
    """`fed.broadcast` is in the source too, but FedAvg's broadcast is a
    view of the server state and emits no operation to carry the name."""
    import flax.linen as nn

    from fedml_tpu.algorithms.builtin import make_fedavg
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.parallel.round import build_round_fn

    net = nn.Dense(3)
    params = net.init(jax.random.key(0), jnp.zeros((1, 16)))["params"]
    alg = make_fedavg(net.apply,
                      TrainArgs(epochs=1, batch_size=2, learning_rate=0.1))
    data, ids, w = _round_args(4, jnp.zeros((4, 16), jnp.float32))
    fn = build_round_fn(alg, mesh=None, health_stats=True)
    names = _op_names(fn.lower(alg.server_init(params, None), jnp.zeros((4,)),
                               data, ids, w, jax.random.key(1), None))
    assert {"fed.local_sgd", "fed.accumulate", "fed.collect", "fed.finalize",
            "fed.health"} <= _leaves(names)
    # the client loop's own stacking, and none of the body's parts, is
    # what stays `fed.collect`
    assert any(n.endswith("fed.collect/while/body/dynamic_update_slice")
               for n in names), sorted(names)[:20]


def test_lora_round_lowering_names_the_lm_and_the_recompute():
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import federated_lora
    from fedml_tpu.parallel.round import build_round_fn

    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True, remat=True)
    base = model.init(jax.random.key(0),
                      jnp.zeros((1, 16), jnp.int32))["params"]
    alg, adapters = federated_lora(
        model, base, TrainArgs(epochs=1, batch_size=2, learning_rate=0.5),
        jax.random.key(1), rank=4)
    data, ids, w = _round_args(4, jnp.zeros((2, 16), jnp.int32))
    fn = build_round_fn(alg, mesh=None)
    names = _op_names(fn.lower(alg.server_init(adapters, None),
                               jnp.zeros((4,)), data, ids, w,
                               jax.random.key(2), None))
    assert {"lm.embed", "lm.attn", "lm.mlp", "lm.head", "fed.local_sgd",
            "fed.accumulate", "fed.finalize"} <= _leaves(names)
    # the forward, the checkpoint's second forward and the backward proper
    # of one part differ by name: `rematted_computation` marks the recompute
    mlp = [n for n in names if "lm.mlp" in n]
    assert any("checkpoint" not in n for n in mlp)
    assert any("rematted_computation" in n for n in mlp)
    assert any("checkpoint" in n and "rematted_computation" not in n
               for n in mlp)


def test_paged_step_and_chunk_lowering_name_the_decode_layers(lm):
    model, params = lm
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN, page_size=8,
                       prefill_chunk=8)
    step = _op_names(eng._step_jit.lower(eng.params, eng.adapters,
                                         eng._carry))
    assert {"decode.kv_write", "decode.attn", "decode.mlp", "decode.head",
            "decode.sample"} <= _leaves(step)
    # what the layer scan itself does is under no decode scope: it slices
    # the stacked weights, and (PR 27) no longer restacks the KV pool
    bare = [n for n in step if "while/body/" in n and not SCOPE.search(n)]
    assert any(n.endswith("/dynamic_slice") for n in bare)
    assert not any(n.endswith("/dynamic_update_slice") for n in bare)
    admit = _op_names(eng._admit_jit.lower(
        eng.params, eng.adapters, eng._carry, jnp.zeros((1, 8), jnp.int32),
        jnp.int32(0), jnp.int32(8), jnp.int32(0),
        jnp.zeros((eng._max_pages,), jnp.int32), jnp.float32(0.0),
        jnp.uint32(0), jnp.int32(9), jnp.bool_(True), jnp.int32(8)))
    assert {"decode.kv_write", "decode.attn", "decode.mlp", "decode.head",
            "decode.sample"} <= _leaves(admit)
