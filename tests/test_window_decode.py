"""What a model of window and global layers forced into the decode programs,
each against its plain form at tiny sizes (two periods of G W W W, window 8,
page 4, 8 experts top 3, ReGLU, a router that reads the attention norm's
output): the module's forward against the float32 reference; `chunk` in
chunks and then `step`, over contexts of several windows, against the
reference's full forward on logits through the window kernel; each of the
reference's three planted faults far from what the program computes; the
windowed kernel (interpreted) against a gather over the same ring pages;
ReGLU in the live-row kernels forward and backward against `jax.grad` of the
plain form; the row moves' gather form at a width whose slab is no whole
tile; and what the decode path now serves and still refuses, by name.

Tolerances: every program here runs in float32, and the reference too, so
the logits differ by the order of summation alone (a ring gathers its keys
in ring order, the expert layer sums its experts' parts as the grouped
product lays them out): about 3e-6 on the CPU. 1e-4 holds that with
room, and the nearest planted fault lies more than 0.1 away."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import smallthinker_21b_pp7 as ref
from fedml_tpu.llm import decode
from fedml_tpu.llm.moe import MoE, _reglu
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.ops import paged_attention as pa
from fedml_tpu.ops import routed_rows as rr
from fedml_tpu.ops.paged_attention import paged_attention

CFG = manifest.load_json(
    manifest.HERE / "configs" / "smallthinker_21b_pp7.json")
MODEL = {**CFG["model"], **CFG["rehearse"]["model"], "num_hidden_layers": 8,
         "rope_layout": [0, 1, 1, 1] * 2,
         "sliding_window_layout": [0, 1, 1, 1] * 2}
PS, C, W, S = 4, 8, 8, 2
RING = -(-(W + C) // PS) + 1                # 5 pages a slot
T, PLEN = 36, 22                            # a context of four windows
MAX_PAGES = -(-T // PS)
TOL = 1e-4


@pytest.fixture(scope="module")
def tiny():
    lm, _spec = manifest.find("models", "smallthinker")(MODEL)
    params = inputs.init_tree(inputs.param_shapes(lm), 11, 1.0, "float32")
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (T,), 1, 127))
    want = np.asarray(ref.forward(params, jnp.asarray(tokens), MODEL))
    return lm, params, tokens, want


def programs(lm, kernel: bool):
    return decode.make_paged_kv_decode(
        lm.n_heads, PS, eps=lm.norm_eps, kernel=kernel,
        rope_base=lm.rope_base, head_dim=lm.head_dim, moe=lm.moe,
        attn_kinds=tuple(a for a, _ in lm.kinds), window=W,
        rope_full=False, ring=RING)


def empty_cache() -> dict:
    """The two global layers' page pool and the six window layers' rings."""
    pool = S * MAX_PAGES + 1
    return {"k": jnp.zeros((2, pool, PS, 2, 16)),
            "v": jnp.zeros((2, pool, PS, 2, 16)),
            "wk": jnp.zeros((6, 1 + S * RING, PS, 2, 16)),
            "wv": jnp.zeros((6, 1 + S * RING, PS, 2, 16))}


def run_programs(lm, params, tokens, kernel: bool) -> np.ndarray:
    """Logits [T - PLEN + 1, V] the engine's programs give: the prompt's
    PLEN tokens by `chunk` in chunks of C into slot 1, then a `step` a token
    (teacher-forced) with slot 0 idle."""
    chunk, step, _verify, _batch = programs(lm, kernel)
    cache = empty_cache()
    stacked = decode.stack_blocks(params, lm.n_layers, True)
    assert isinstance(stacked["blocks"], tuple)
    slot = 1
    row = 1 + slot * MAX_PAGES + np.arange(MAX_PAGES, dtype=np.int32)
    chunk, step = jax.jit(chunk), jax.jit(step)
    t = 0
    while t < PLEN:
        n = min(C, PLEN - t)
        buf = np.zeros((1, C), np.int32)
        buf[0, :n] = tokens[t:t + n]
        cache, logits, _ = chunk(stacked, None, cache, row, buf, t, n, slot)
        t += n
    got = [np.asarray(logits[0])]
    pages = np.zeros((S, MAX_PAGES), np.int32)
    pages[slot] = row
    active = np.array([False, True])
    for pos in range(PLEN, T - 1):
        cache, logits, _ = step(stacked, None, cache, pages,
                                np.array([0, pos], np.int32),
                                np.array([0, tokens[pos]], np.int32), active)
        got.append(np.asarray(logits[slot]))
    return np.stack(got)


@pytest.fixture(scope="module")
def served(tiny):
    """The kernel's programs (the step's gathered ring is the engine
    tests' `paged_kernel=False` case, token for token)."""
    lm, params, tokens, _want = tiny
    return run_programs(lm, params, tokens, kernel=True)


# ---------------------------------------------------------------- the module
def test_the_modules_forward_is_the_references(tiny):
    lm, params, tokens, want = tiny
    got = np.asarray(lm.apply({"params": params}, tokens[None])[0])
    assert np.abs(got - want).max() < TOL
    # two periods: a global layer at 0 and 4, window layers between
    assert [a for a, _ in lm.kinds] == ["full", "window", "window",
                                        "window"] * 2
    assert not lm.rope_full and lm.window == W
    assert (lm.moe.act, lm.moe.route_from) == ("reglu", "attn")


# ------------------------------------------------------ the decode programs
def test_chunks_then_steps_through_the_ring_are_the_references(tiny, served):
    _lm, _params, _tokens, want = tiny
    assert np.abs(served - want[PLEN - 1:T - 1]).max() < TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_is_far_from_what_the_program_serves(
        tiny, served, fault):
    """A window layer that attends every key, global layers rotated, a
    router that reads the feed-forward's input: each moves the logits the
    program computes by far more than the tolerance."""
    _lm, params, tokens, _want = tiny
    theirs = np.asarray(ref.forward(params, jnp.asarray(tokens), MODEL,
                                    fault=fault))[PLEN - 1:T - 1]
    assert np.abs(served - theirs).max() > 0.1


def test_a_stacked_tree_is_refused_for_a_model_of_two_kinds(tiny):
    lm, params, tokens, _want = tiny
    chunk, *_ = decode.make_paged_kv_decode(
        lm.n_heads, PS, head_dim=lm.head_dim, moe=lm.moe,
        attn_kinds=tuple(a for a, _ in lm.kinds), window=W,
        rope_full=False, ring=RING)
    dense = TransformerLM(vocab_size=8, d_model=16, n_layers=2, n_heads=2,
                          d_ff=8, window=4, rope_full=False,
                          layer_kinds=(("full", "dense"), ("window", "dense")))
    p = dense.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert not isinstance(decode.stack_blocks(p, 2)["blocks"], tuple)
    assert isinstance(decode.stack_blocks(p, 2, True)["blocks"], tuple)
    # a stack turns into the layers' tuple when asked
    again = decode.stack_blocks(decode.stack_blocks(p, 2), 2, True)
    assert isinstance(again["blocks"], tuple) and len(again["blocks"]) == 2
    with pytest.raises(NotImplementedError, match="int8 ring pages"):
        decode.make_paged_kv_decode(2, PS, attn_kinds=("full", "window"),
                                    window=4, ring=3, quant=True)


# --------------------------------------------------------- the window kernel
# Positions of four slots (and which are live) over a window of 8 (the
# kernel's own block, one of the whole ring) and of 64 at page 4 in blocks
# of 4 pages: a ring of 18 pages, four blocks and a half, so that a walk
# has whole blocks (one DMA of K and one of V) and, past the first window,
# one block that runs past the ring's last page (copied page by page).
WALKS = {
    "phases": (8, None, [2, 9, 23, 40], [True, True, False, True]),
    "mid_block": (64, 4, [86, 101, 150, 230], None),
    "ring_end": (64, 4, [75, 139, 143, 300], None),
    "short": (64, 4, [0, 5, 9, 12], None),
    "exact_window": (64, 4, [63, 63, 127, 255], None),
    "phases_64": (64, 4, [2, 63, 85, 200], [True, True, False, True]),
}


@pytest.mark.parametrize("queries, case", [(1, "phases"), (3, "phases")] + [
    (queries, case) for case in list(WALKS)[1:] for queries in (1, 3)],
    ids=["1", "3"] + [f"{case}-{queries}" for case in list(WALKS)[1:]
                      for queries in (1, 3)])
def test_the_window_kernel_is_a_gather_over_the_same_ring(queries, case,
                                                          monkeypatch):
    """Random pages in a pool of rings, four slots at positions before, at
    and well past the ring's first turn (one retired where the case says):
    the kernel's rows equal attention over the keys pos + i - W + 1 ..
    pos + i gathered in virtual order from the ring."""
    w, block, pos, active = WALKS[case]
    heads, kv, dh, ps = 4, 2, 8, 4
    ring = -(-(w + queries) // ps) + 1
    slots = 4
    if block:
        monkeypatch.setattr(pa, "_WINDOW_BLOCK_PAGES", block)
    block = pa.window_block_pages(ring)
    ks = jax.random.split(jax.random.key(3), 3)
    k_pool = jax.random.normal(ks[0], (1 + slots * ring, ps, kv, dh))
    v_pool = jax.random.normal(ks[1], (1 + slots * ring, ps, kv, dh))
    q = jax.random.normal(ks[2], (slots, queries, heads, dh))
    table = 1 + np.arange(slots)[:, None] * ring + np.arange(ring)[None]
    pos = np.array(pos, np.int32)
    active = np.array(active or [True] * slots)
    walks = [pa.ring_walk(int(p), queries, w, ps, ring) for p in pos[active]]
    if case == "mid_block":
        assert all((p - w + 1) // ps % block for p in pos)
    if case in ("mid_block", "ring_end", "phases_64"):
        # both DMA plans: whole blocks, and a block split at the ring's end
        assert any(n > cut for n, cut in walks)
        assert any(cut for _n, cut in walks)
    if case == "short":
        assert pos.max() + queries <= block * ps and walks == [(1, 0)] * 4
    if case == "exact_window":
        assert pos[0] + 1 == w
    pa._call.clear_cache()         # the block is read when `_call` traces
    try:
        got = np.asarray(paged_attention(q, k_pool, v_pool, table, pos,
                                         active=active, window=w))
    finally:
        pa._call.clear_cache()
    for s in np.nonzero(active)[0]:
        for i in range(queries):
            at = pos[s] + i
            keys = np.arange(max(0, at - w + 1), at + 1)
            pages = table[s, (keys // ps) % ring]
            kk = np.asarray(k_pool)[pages, keys % ps]        # [n, kv, dh]
            vv = np.asarray(v_pool)[pages, keys % ps]
            for h in range(heads):
                sc = kk[:, h // (heads // kv)] @ np.asarray(q[s, i, h]) \
                    * dh ** -0.5
                p = np.exp(sc - sc.max())
                want = (p / p.sum()) @ vv[:, h // (heads // kv)]
                assert np.abs(got[s, i, h] - want).max() < 1e-4
    for s in np.nonzero(~active)[0]:
        assert not got[s].any()                  # a retired slot: zeros


def test_the_step_hands_the_window_kernel_consecutive_ring_pages(
        tiny, monkeypatch):
    """The window kernel reads a block of ring pages as ONE range of the
    pool, so every row of the ring table the step hands it (`ring_pages`
    over arange(R), the layer's base included) must be consecutive page
    ids: row s of window layer l runs from l * (1 + S * R) + 1 + s * R."""
    lm, params, tokens, _want = tiny
    seen, real = [], pa.paged_attention

    def spy(q, k, v, pages, pos, *rest, window=0, **kw):
        if window:
            jax.debug.callback(lambda p: seen.append(np.asarray(p)), pages)
        return real(q, k, v, pages, pos, *rest, window=window, **kw)

    monkeypatch.setattr(pa, "paged_attention", spy)
    _chunk, step, _verify, _batch = programs(lm, kernel=True)
    pages = np.zeros((S, MAX_PAGES), np.int32)
    pages[1] = 1 + MAX_PAGES + np.arange(MAX_PAGES)
    jax.block_until_ready(jax.jit(step)(
        decode.stack_blocks(params, lm.n_layers, True), None, empty_cache(),
        pages, np.array([0, 30], np.int32), np.array([0, tokens[30]],
                                                     np.int32),
        np.array([False, True])))
    assert len(seen) == 6                        # one a window layer
    bases = sorted(int(t[0, 0]) - 1 for t in seen)
    assert bases == [l * (1 + S * RING) for l in range(6)]
    for t in seen:
        assert t.shape == (S, RING)
        assert (t == t[0, 0] + np.arange(S)[:, None] * RING
                + np.arange(RING)).all()


def test_the_window_kernel_refuses_what_it_is_not():
    q = jnp.zeros((1, 1, 2, 8))
    pool = jnp.zeros((3, 4, 2, 8))
    with pytest.raises(NotImplementedError, match="int8 ring pages"):
        paged_attention(q, pool, pool, np.ones((1, 2), np.int32), 0,
                        jnp.ones((3, 2)), jnp.ones((3, 2)), window=4)


# --------------------------------------------------- the expert layer's unit
def test_reglu_in_the_live_row_kernel_forward_and_backward():
    gate = jax.random.normal(jax.random.key(0), (64, 32))
    up = jax.random.normal(jax.random.key(1), (64, 32))
    live = jnp.int32(64)
    plain = lambda g, u: jnp.sum(jnp.sin(jax.nn.relu(g) * u))
    mine = lambda g, u: jnp.sum(jnp.sin(_reglu(g, u, live)))
    assert jnp.allclose(_reglu(gate, up, live), jax.nn.relu(gate) * up,
                        atol=1e-6)
    for a, b in zip(jax.grad(mine, (0, 1))(gate, up),
                    jax.grad(plain, (0, 1))(gate, up)):
        assert jnp.allclose(a, b, atol=1e-5)


def test_the_expert_layer_routes_from_what_it_is_handed():
    spec = MoE(n_experts=4, top_k=2, d_expert=8, n_shared=0,
               scoring="softmax", act="reglu", route_from="attn")
    from fedml_tpu.llm.moe import ExpertLayer

    h = jax.random.normal(jax.random.key(0), (1, 6, 16))
    x = jax.random.normal(jax.random.key(1), (1, 6, 16))
    layer = ExpertLayer(spec)
    p = layer.init(jax.random.key(2), h, route_x=x)
    a = layer.apply(p, h, route_x=x, mutable=["counters"])[0]
    b = layer.apply(p, h, route_x=h, mutable=["counters"])[0]
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    with pytest.raises(ValueError, match="takes `route_x`"):
        layer.apply(p, h, mutable=["counters"])
    with pytest.raises(ValueError, match="act must be 'swiglu' or 'reglu'"):
        MoE(n_experts=4, top_k=2, d_expert=8, act="gelu")


def test_row_moves_of_a_width_whose_slab_is_no_whole_tile_are_gathers():
    """Rows of 256 bfloat16 numbers are two pieces of 128: no whole tile of
    16 sublanes, so the moves are XLA gathers with the kernels' values."""
    n, k, d = 12, 2, 256
    x = jax.random.normal(jax.random.key(0), (n, d)).astype(jnp.bfloat16)
    assert not rr._tiled(d, jnp.bfloat16) and rr._tiled(2048, jnp.bfloat16)
    order = jnp.asarray(np.random.RandomState(0).permutation(n * k),
                        jnp.int32)
    live = jnp.int32(15)
    out = rr.rows_out(x, order // k, live, jnp.full((n * k,), 2.0))
    want = jnp.where((jnp.arange(n * k) < 15)[:, None],
                     2.0 * x[order // k].astype(jnp.float32), 0.0)
    assert jnp.allclose(out.astype(jnp.float32), want, atol=1e-2)
    inv = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    ok = inv < 15
    w = jax.random.uniform(jax.random.key(1), (n, k))
    src = jnp.where(jnp.isfinite(out), out, 0).at[15:].set(jnp.nan)
    back = rr.rows_back(src, inv, ok, w, live).astype(jnp.float32)
    plain = jnp.sum(jnp.where(ok[..., None], w[..., None]
                              * src[inv].astype(jnp.float32), 0.0), 1)
    assert jnp.allclose(back, plain, atol=5e-2)
    dots = rr.rows_dots(src, inv, ok, x, live)
    assert jnp.all(jnp.isfinite(dots)) and not dots[~ok].any()


# ------------------------------------------------ served now, refused by name
def test_the_decode_path_serves_window_and_unrotated_layers():
    lm, _spec = manifest.find("models", "smallthinker")(MODEL)
    assert decode.unserved(lm) == []
    decode.require_servable(lm)
    assert decode.engine_only(lm) == (
        "window layers or full layers without rotary positions; grouped KV "
        "heads, per-head q/k norms or experts")
