"""A model of window and global layers through the decode engine at tiny
sizes (one period of G W W W, window 8, page 4, prefill chunk 8, 8 experts
top 3): greedy tokens over prompts of several windows against the float32
reference's, with the window kernel and with the gather, batched admission
and speculative windows; each slot's ring sized by the formula and reused as
it advances, the pages the window layers hold never past it; the window
counters by hand; and what is refused by name on such a model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import smallthinker_21b_pp7 as ref
from fedml_tpu.ops.paged_attention import ring_walk, window_block_pages
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.utils import metrics as mx

CFG = manifest.load_json(
    manifest.HERE / "configs" / "smallthinker_21b_pp7.json")
MODEL = {**CFG["model"], **CFG["rehearse"]["model"], "num_hidden_layers": 4,
         "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1]}
PS, CHUNK, W, SLOTS, MAX_LEN = 4, 8, 8, 2, 48
RING = -(-(W + CHUNK) // PS) + 1
PROMPTS = [(29, 9), (13, 14), (4, 6)]      # (prompt, new): up to 38 positions


@pytest.fixture(scope="module")
def tiny():
    lm, _spec = manifest.find("models", "smallthinker")(MODEL)
    params = inputs.init_tree(inputs.param_shapes(lm), 5, 1.0, "float32")
    rs = np.random.RandomState(2)
    prompts = [[int(t) for t in rs.randint(1, 127, n)] for n, _ in PROMPTS]
    greedy = []
    for toks, (_n, new) in zip(prompts, PROMPTS):
        seq = list(toks)
        for _ in range(new):
            ids = np.zeros((MAX_LEN,), np.int32)
            ids[:len(seq)] = seq
            at = ref.forward(params, jnp.asarray(ids), MODEL,
                             rows=[len(seq) - 1])
            seq.append(int(jnp.argmax(at[0])))
        greedy.append(seq[len(toks):])
    return lm, params, prompts, greedy


def serve_all(lm, params, prompts, **kw):
    """Every prompt at once into a 2-slot engine; (tokens, engine, the
    window-pages gauge after each frame)."""
    eng = DecodeEngine(lm, params, n_slots=SLOTS, max_len=MAX_LEN,
                       page_size=PS, prefill_chunk=CHUNK, prefix_cache=False,
                       **kw)
    held = []
    count = eng._count_window_pages

    def counting():
        count()
        held.append(mx.snapshot()["gauges"]["serving.engine.window_pages"])

    eng._count_window_pages = counting
    eng.start()
    try:
        tickets = [eng.submit(t, new) for t, (_n, new) in zip(prompts,
                                                               PROMPTS)]
        return [t.result(timeout=600) for t in tickets], eng, held
    finally:
        eng.stop()


def counted(tiny, **kw):
    """serve_all, and what it added to the engine's counters."""
    lm, params, prompts, _greedy = tiny
    before = mx.snapshot()["counters"]
    got, eng, held = serve_all(lm, params, prompts, **kw)
    after = mx.snapshot()["counters"]
    delta = {k[len("serving.engine."):]: after[k] - before.get(k, 0)
             for k in after if k.startswith("serving.engine.")}
    return got, eng, held, delta


@pytest.fixture(scope="module")
def with_kernel(tiny):
    return counted(tiny, paged_kernel=True)


@pytest.mark.parametrize("kw", [
    {"paged_kernel": True},
    {"admit_batch": 2, "spec_decode": "ngram", "spec_k": 2}],
    ids=["kernel", "gather-batched-spec"])
def test_greedy_tokens_over_several_windows_are_the_references(
        tiny, with_kernel, kw):
    greedy = tiny[3]
    got, eng, held, delta = (with_kernel if kw.get("paged_kernel")
                             else counted(tiny, **kw))
    assert got == greedy
    # each slot owns a ring of ceil((W + C) / page) + 1 pages, whatever
    # its context; the window layers never hold more
    widest = max(CHUNK, kw.get("spec_k", 0) + 1)
    assert eng._ring == -(-(W + widest) // PS) + 1 == RING
    assert eng._carry["cache"]["wk"].shape == (3, 1 + SLOTS * RING, PS, 2, 16)
    assert eng._carry["cache"]["k"].shape[0] == 1
    assert held and max(held) <= 3 * SLOTS * RING
    # the 38-position request passes its ring (20 positions) twice: the
    # gauge reached a whole ring for it
    assert max(held) >= 3 * RING
    if "spec_k" in kw:
        # the gather walks no ring through the kernel
        assert delta.get("window_blocks", 0) == 0
        return
    # the window counters: a query at every position but each request's
    # last token's, attending at most the window in a window layer
    at = np.concatenate([np.arange(1, n + new) for n, new in PROMPTS])
    assert delta["window_context_keys"] == delta["context_keys"] == at.sum()
    assert delta["window_keys"] == np.minimum(at, W).sum()


def test_the_window_walk_counters_follow_the_positions(with_kernel):
    """`window_blocks` / `window_split_blocks`: the blocks of the window
    kernel's ring walk over every step a request takes (its first token is
    the prefill's; a step's query sits on each later token's predecessor,
    positions n .. n + new - 2), and those that run past the ring's last
    page, by the kernel's own arithmetic on positions. The ring of 5 pages
    is one block here, so a walk past the first window splits."""
    delta = with_kernel[3]
    walks = [ring_walk(p, 1, W, PS, RING) for n, new in PROMPTS
             for p in range(n, n + new - 1)]
    assert window_block_pages(RING) == RING
    assert delta["window_blocks"] == sum(b for b, _ in walks) > 0
    assert delta["window_split_blocks"] == sum(c for _, c in walks) > 0
    # a walk that starts at the ring's first page is one whole block
    assert any(b and not c for b, c in walks)


def test_what_a_model_with_window_layers_refuses_by_name(tiny):
    lm, params, _prompts, _greedy = tiny
    for kw, name in (({"prefix_cache": True}, "prefix sharing"),
                     ({"kv_quant": "int8"}, "kv_quant='int8'"),
                     ({"mesh": jax.sharding.Mesh(
                         np.array(jax.devices()[:1]), ("mp",))},
                      "a mesh")):
        with pytest.raises(NotImplementedError,
                           match=f"{name}.*window layers.*private ring"):
            DecodeEngine(lm, params, n_slots=1, max_len=16, page_size=PS,
                         **{"prefix_cache": False, **kw})


def test_start_replica_refuses_prefix_sharing_on_window_layers(tiny):
    from fedml_tpu.serving.predictor import GreedyLMPredictor
    from fedml_tpu.serving.scheduler import start_replica

    lm, params, _prompts, _greedy = tiny
    _module, spec = manifest.find("models", "smallthinker")(MODEL)
    with pytest.raises(NotImplementedError, match="prefix sharing on a model "
                       "with window layers.*private to its slot"):
        start_replica({**spec, "params": params, "port": 0,
                       "serve": {"decode_slots": 1, "kv_page_size": PS}})
    with pytest.raises(NotImplementedError, match="window layers or full "
                       "layers without rotary positions; grouped KV heads.*"
                       "served by the decode engine only"):
        GreedyLMPredictor(lm, params)


def test_a_stack_of_unrotated_full_layers_is_served_as_a_stack():
    """Full layers without rotary positions and no window layer: the layers
    stay one stack under the scan, the prefix cache stays on, and the
    engine's greedy tokens are the module's."""
    from fedml_tpu.llm.transformer import TransformerLM

    lm = TransformerLM(vocab_size=40, d_model=32, n_layers=2, n_heads=4,
                       d_ff=64, rope_full=False)
    toks = [int(v) for v in np.random.RandomState(0).randint(1, 40, 9)]
    params = lm.init(jax.random.key(0), jnp.asarray([toks]))["params"]
    seq = list(toks)
    whole = jax.jit(lambda ids: lm.apply({"params": params}, ids))
    for _ in range(6):
        ids = jnp.asarray([seq + [0] * (16 - len(seq))])
        seq.append(int(jnp.argmax(whole(ids)[0, len(seq) - 1])))
    eng = DecodeEngine(lm, params, n_slots=2, max_len=32, page_size=4,
                       prefill_chunk=8, paged_kernel=True).start()
    try:
        assert eng.submit(toks, 6).result(timeout=300) == seq[9:]
        assert not isinstance(eng.params["blocks"], tuple)
        assert set(eng._carry["cache"]) == {"k", "v"}
    finally:
        eng.stop()
