"""The decode engine's dense weights, laid out once at load
(llm/decode.py `lay_out_blocks`): wq, wk and wv become ONE kernel `wqkv`
[L, d, G, C] that the programs' q/k/v product reads in place, where the layer
scan sliced and re-laid each of the three every layer (PERF.md section 6).

- PARITY: in each of the four paged programs, float and int8 bases, with and
  without LoRA adapters on the three projections, the laid-out tree gives
  the stacked tree's logits and pool, and the per-request oracle
  (`make_kv_decode`) serves both trees alike; one column group a device of
  an `mp` axis keeps every group's [q | k | v] columns in place.
- THE ENGINE: it holds the laid-out tree and counts its bytes in the gauge
  `serving.engine.weights_laid_out_bytes`; an adapter hot swap over it
  serves what a per-request predictor on the stacked tree serves; a tuple of
  layers (expert layers) is left as it lies and the gauge reads 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import decode as dec
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.moe import MoE
from fedml_tpu.llm.quant import quantize_tree_int8
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.utils import metrics as mx

# d 64 over 2 layers: each stacked q/k/v kernel holds 8,192 numbers, over
# quant._MIN_QUANT_SIZE, so the int8 base quantizes all three
V, D, L, H, FF = 64, 64, 2, 4, 128
DH = D // H
PS, MAX_PAGES, SLOTS, C = 4, 4, 3, 8
P = SLOTS * MAX_PAGES + 1
PROGRAMS = ("chunk", "step", "verify", "chunk_batch")
GAUGE = "serving.engine.weights_laid_out_bytes"


def _model():
    return TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                         d_ff=FF, scan_layers=True)


@pytest.fixture(scope="module")
def base():
    return _model().init(jax.random.key(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]


def _adapters(params):
    """Rank-2 adapters on wq/wk/wv/wo whose B is NOT zero, so every delta
    moves the product."""
    ads = lora_init(jax.random.key(1), params, rank=2)
    return {k: {"a": v["a"] * 10.0,
                "b": 0.05 * jax.random.normal(
                    jax.random.key(hash(k) % 997), v["b"].shape)}
            for k, v in ads.items()}


def _cache(quant):
    z = (L, P, PS, H, DH)
    ks = jax.random.split(jax.random.key(2), 4)
    if not quant:
        return {"k": jax.random.normal(ks[0], z),
                "v": jax.random.normal(ks[1], z)}
    return {"k": jax.random.randint(ks[0], z, -127, 128, jnp.int8),
            "v": jax.random.randint(ks[1], z, -127, 128, jnp.int8),
            "ks": jax.random.uniform(ks[2], (L, P, H)) / 127.0,
            "vs": jax.random.uniform(ks[3], (L, P, H)) / 127.0}


def _program_args(name, cache):
    pages = jnp.arange(1, P, dtype=jnp.int32).reshape(SLOTS, MAX_PAGES)
    rs = np.random.RandomState(3)
    pos = jnp.asarray(rs.randint(0, 6, SLOTS), jnp.int32)
    on = jnp.asarray([True, False, True])
    if name == "chunk":
        toks = jnp.asarray(rs.randint(1, V, (1, C)), jnp.int32)
        return (cache, pages[0], toks, 4, C - 1)
    if name == "step":
        return (cache, pages, pos, jnp.asarray(rs.randint(1, V, SLOTS),
                                                jnp.int32), on)
    if name == "verify":
        return (cache, pages, pos,
                jnp.asarray(rs.randint(1, V, (SLOTS, 3)), jnp.int32), on)
    return (cache, pages, jnp.asarray(rs.randint(1, V, (SLOTS, C)),
                                      jnp.int32),
            pos, jnp.asarray([C, 0, 5], jnp.int32))


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("name", PROGRAMS)
def test_the_laid_out_tree_serves_as_the_stacked_tree(base, name, quant,
                                                      lora):
    stacked = quantize_tree_int8(base) if quant else base
    laid = dec.lay_out_blocks(stacked)
    blocks = laid["blocks"]
    assert "wqkv" in blocks and not set(dec.QKV) & set(blocks)
    want_form = ({"q": (L, D, 1, 3 * D), "s": (L, 1, 1, 3 * D)} if quant
                 else (L, D, 1, 3 * D))
    assert jax.tree.map(lambda a: a.shape, blocks["wqkv"]["kernel"]) == \
        want_form
    ads = _adapters(base) if lora else None
    # the gather path: what attends the pool is the same for both trees
    programs = dict(zip(PROGRAMS, dec.make_paged_kv_decode(
        H, PS, quant=quant)))
    args = _program_args(name, _cache(quant))
    run = jax.jit(lambda p, a, *rest: programs[name](p, a, *rest))
    cache_s, logits_s = run(stacked, ads, *args)
    cache_l, logits_l = run(laid, ads, *args)
    np.testing.assert_allclose(logits_l, logits_s, rtol=2e-5, atol=2e-5)
    for leaf in cache_s:
        np.testing.assert_allclose(
            np.asarray(cache_l[leaf], np.float32),
            np.asarray(cache_s[leaf], np.float32), rtol=2e-5, atol=2e-5)
    if name != "chunk":
        return
    # the per-request oracle takes either tree (once a base and adapters)
    prefill, step = dec.make_kv_decode(H)
    toks = jnp.asarray(np.random.RandomState(4).randint(1, V, (1, 6)))
    (c_s, l_s), (c_l, l_l) = (jax.jit(prefill, static_argnums=3)(
        p, ads, toks, 16) for p in (stacked, laid))
    np.testing.assert_allclose(l_l, l_s, rtol=2e-5, atol=2e-5)
    nxt = jnp.argmax(l_s, -1).astype(jnp.int32)
    np.testing.assert_allclose(
        jax.jit(step)(laid, ads, c_l, 6, nxt)[1],
        jax.jit(step)(stacked, ads, c_s, 6, nxt)[1], rtol=2e-5, atol=2e-5)


def test_a_column_group_a_device_keeps_each_groups_columns(base):
    """G groups: group g holds wq's, wk's and wv's g-th share of columns,
    in that order, which is what a column split over G devices gives each."""
    g = 2
    laid = dec.lay_out_blocks(base, g)["blocks"]["wqkv"]["kernel"]
    assert laid.shape == (L, D, g, 3 * D // g)
    for i in range(g):
        for j, n in enumerate(dec.QKV):
            share = base["blocks"][n]["kernel"][..., i * D // g:
                                                (i + 1) * D // g]
            np.testing.assert_array_equal(
                laid[:, :, i, j * D // g:(j + 1) * D // g], share)
    # widths G does not divide, and trees laid out already, pass through
    assert "wq" in dec.lay_out_blocks(base, 3)["blocks"]
    once = dec.lay_out_blocks(base)
    assert dec.lay_out_blocks(once) is once


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def swap_setup(base):
    a1 = _adapters(base)
    a2 = jax.tree.map(lambda a: -0.7 * a, a1)
    # no prefix cache: a prompt's pages cached before a swap hold keys and
    # values of the old adapters, and a later request would reuse them
    eng = DecodeEngine(_model(), base, adapters=a1, n_slots=2, max_len=32,
                       page_size=4, prefill_chunk=8, prefix_cache=False)
    laid = jax.tree.leaves(eng.params["blocks"]["wqkv"])
    gauge = mx.snapshot()["gauges"][GAUGE]
    yield eng.start(), a2, laid, gauge
    eng.stop()


def test_the_engine_lays_out_once_and_a_hot_swap_serves_the_new_adapters(
        base, swap_setup):
    eng, a2, laid, gauge = swap_setup
    assert "wq" not in eng.params["blocks"]
    assert gauge == sum(a.nbytes for a in laid) == L * D * 3 * D * 4
    prompt = np.random.RandomState(5).randint(1, V, 7).tolist()
    before = eng.submit(prompt, 6).result(timeout=120)
    counts = eng.program_counts()
    assert eng.swap_adapters(a2) == 1
    got = eng.submit(prompt, 6).result(timeout=120)
    # a per-request replica built on the STACKED tree and the new adapters
    want = GreedyLMPredictor(_model(), base, adapters=a2, max_len=32,
                             kv_cache=True).predict(
        {"tokens": prompt, "max_new_tokens": 6})["generated_tokens"]
    assert got == want and got != before
    assert eng.program_counts() == counts, "the swap retraced a program"


def test_a_tuple_of_layers_is_left_as_it_lies():
    """Expert layers are not stacked (`stack_blocks` gives their tuple), so
    nothing is laid out and the gauge reads 0."""
    lm = TransformerLM(vocab_size=V, d_model=32, n_layers=2, n_heads=2,
                       d_ff=64, moe=MoE(n_experts=4, top_k=2, d_expert=16),
                       layer_kinds=(("full", "moe"), ("full", "moe")))
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    eng = DecodeEngine(lm, params, n_slots=2, max_len=16, page_size=4)
    assert isinstance(eng.params["blocks"], tuple)
    assert all("wq" in layer for layer in eng.params["blocks"])
    assert mx.snapshot()["gauges"][GAUGE] == 0
