"""The pieces a block-diffusion expert model forced into the program, each
against its plain form at tiny sizes: the block-causal mask on the module's
forward against the reference; `make_paged_kv_decode`'s `chunk` and window
program (grouped KV heads, per-head q/k norms, the expert layer) against the
module's forward; the paged kernel (interpreted) against the gather at 8
query heads a KV head under a causal and a both-ways window; the softmax
router's shares summing to the uncut layer; and the defaults of `MoE`,
`TransformerLM` and `dense_causal_attention` lowering as before."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import sdar_30b_a3b_pp8 as ref
from fedml_tpu.llm import decode
from fedml_tpu.llm.moe import COUNTERS, ExpertLayer, MoE, route
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.ops.paged_attention import paged_attention
from fedml_tpu.parallel.seq import dense_causal_attention

CFG = manifest.load_json(manifest.HERE / "configs" / "sdar_30b_a3b_pp8.json")
MODEL = {**CFG["model"], **CFG["rehearse"]["model"]}
PS, MAX_PAGES = 4, 8


@pytest.fixture(scope="module")
def tiny():
    lm, _spec = manifest.find("models", "sdar_moe")(MODEL)
    params = inputs.init_tree(inputs.param_shapes(lm), 11, 1.0, "float32")
    tokens = jax.random.randint(jax.random.key(1), (1, 20), 1, 127)
    return lm, params, tokens


@pytest.fixture(scope="module")
def programs(tiny):
    lm, params, _tokens = tiny
    chunk, _step, window, chunk_batch = decode.make_paged_kv_decode(
        lm.n_heads, PS, eps=lm.norm_eps, rope_base=lm.rope_base,
        head_dim=lm.head_dim, qk_norm=True, moe=lm.moe,
        block=lm.diffusion_block)
    stacked = decode.stack_blocks(params, lm.n_layers)
    cache = {k: jnp.zeros((lm.n_layers, 1 + MAX_PAGES, PS, 2, 8))
             for k in ("k", "v")}
    return jax.jit(chunk), jax.jit(window), jax.jit(chunk_batch), stacked, \
        cache


# ---------------------------------------------------------------- the module
def test_the_modules_forward_is_the_references_under_the_block_mask(tiny):
    lm, params, tokens = tiny
    got = lm.apply({"params": params}, tokens)[0]
    want = ref.forward(params, tokens[0], MODEL)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    # and not the causal model's: position 0 sees its block's later tokens
    causal = TransformerLM(**{**{
        f: getattr(lm, f) for f in (
            "vocab_size", "d_model", "n_layers", "n_heads", "d_ff",
            "n_kv_heads", "head_dim", "norm_eps", "rope_base", "qk_norm",
            "moe", "layer_kinds")}}).apply({"params": params}, tokens)[0]
    assert float(jnp.max(jnp.abs(got[0] - causal[0]))) > 1e-2


def test_the_block_mask_is_causal_over_blocks_and_both_ways_inside_one():
    q = jax.random.normal(jax.random.key(0), (1, 8, 2, 4))
    got = dense_causal_attention(q, q, q, block=4)
    # a block's rows attend the same keys: rows of one block differ by q only
    mask = ref.block_mask(8, 4)
    assert mask[0, 3] and not mask[3, 4] and mask[4, 0] and mask[4, 7]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, q) * 0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e9), -1)
    assert jnp.allclose(got, jnp.einsum("bhqk,bkhd->bqhd", p, q), atol=1e-6)


# ------------------------------------------------------ the decode programs
def test_chunk_and_window_are_the_modules_block_causal_forward(tiny,
                                                               programs):
    """Prefill 16 tokens (four blocks) by `chunk` in two pieces, then run the
    window program over the fifth block: its logits are the module's at
    those positions (each over its own token), and the chunk's pages hold
    what a whole forward would have."""
    lm, params, tokens = tiny
    chunk, window, _cb, stacked, cache = programs
    row = jnp.arange(1, 1 + MAX_PAGES, dtype=jnp.int32)
    cache, _l, counted = chunk(stacked, None, cache, row, tokens[:, :8], 0, 8)
    assert int(counted["moe_pairs"]) == 8 * 2 * 2
    padded = jnp.pad(tokens[:, 8:16], ((0, 0), (0, 8)))
    cache, _l, counted = chunk(stacked, None, cache, row, padded, 8, 8)
    assert int(counted["moe_pairs"]) == 8 * 2 * 2       # padding not routed
    full = lm.apply({"params": params}, tokens)[0]
    cache, logits, counted = window(
        stacked, None, cache, row[None], jnp.asarray([16]), tokens[:, 16:20],
        jnp.asarray([True]))
    assert float(jnp.max(jnp.abs(logits[0] - full[16:20]))) < 2e-4
    assert int(counted["moe_pairs"]) == 4 * 2 * 2
    assert 2 <= int(counted["moe_experts_live"]) <= 2 * 8
    # 8 rows a layer are one tile: every live expert's product is that tile
    assert int(counted["moe_tile_rows"]) == 8 * int(
        counted["moe_experts_live"])
    # an idle slot routes nothing and reads no expert
    _c, _l, idle = window(
        stacked, None, cache, row[None], jnp.asarray([16]), tokens[:, 16:20],
        jnp.asarray([False]))
    assert int(idle["moe_pairs"]) == 0 and int(idle["moe_experts_live"]) == 0
    assert int(idle["moe_tile_rows"]) == 0


def test_chunk_batch_is_chunk_a_row_at_a_time(tiny, programs):
    _lm, _params, tokens = tiny
    chunk, _w, chunk_batch, stacked, cache = programs
    row = jnp.arange(1, 1 + MAX_PAGES, dtype=jnp.int32)
    one, logits1, _ = chunk(stacked, None, cache, row, tokens[:, :8], 0, 8)
    rows = jnp.stack([row, jnp.zeros_like(row)])
    two, logits2, _ = chunk_batch(
        stacked, None, cache, rows, jnp.concatenate(
            [tokens[:, :8], jnp.zeros((1, 8), jnp.int32)]),
        jnp.asarray([0, 0]), jnp.asarray([8, 0]))
    assert jnp.allclose(logits1[0], logits2[0], atol=1e-5)
    assert jnp.allclose(one["k"][:, 1:3], two["k"][:, 1:3], atol=1e-6)


def test_a_window_is_one_block_and_a_step_has_no_meaning(programs):
    _c, window, _cb, stacked, cache = programs
    with pytest.raises(ValueError, match="one block of 4"):
        window(stacked, None, cache, jnp.zeros((1, MAX_PAGES), jnp.int32),
               jnp.asarray([0]), jnp.zeros((1, 3), jnp.int32),
               jnp.asarray([True]))


# ------------------------------------------------------------ the paged kernel
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("queries", [1, 4])
def test_the_kernel_is_the_gather_at_eight_query_heads_a_kv_head(causal,
                                                                 queries):
    rs = np.random.RandomState(queries + 2 * causal)
    s_, h, kv, dh, pages = 3, 16, 2, 8, 6
    q = jnp.asarray(rs.randn(s_, queries, h, dh), jnp.float32)
    kp = jnp.asarray(rs.randn(1 + s_ * pages, PS, kv, dh), jnp.float32)
    vp = jnp.asarray(rs.randn(1 + s_ * pages, PS, kv, dh), jnp.float32)
    table = jnp.asarray(1 + np.arange(s_ * pages).reshape(s_, pages),
                        jnp.int32)
    pos = jnp.asarray([8, 0, 16], jnp.int32)
    active = jnp.asarray([True, True, False])
    got = paged_attention(q, kp, vp, table, pos, active=active,
                          causal=causal, interpret=True)
    kk = kp[table].reshape(s_, pages * PS, kv, dh)
    vv = vp[table].reshape(s_, pages * PS, kv, dh)
    qpos = pos[:, None] + jnp.arange(queries)
    kpos = jnp.arange(pages * PS)
    seen = (kpos[None, None, :] <= qpos[:, :, None] if causal else
            kpos[None, None, :] < (pos[:, None, None] + queries))
    qg = q.reshape(s_, queries, kv, h // kv, dh)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk) * dh ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, None], sc, -1e30), -1)
    want = jnp.einsum("bkgqs,bskd->bqkgd", p, vv).reshape(q.shape)
    assert float(jnp.max(jnp.abs(got[:2] - want[:2]))) < 1e-5
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0       # not active: zeros


# ------------------------------------------------------------------ the router
def test_the_softmax_router_chooses_by_probability_and_renormalises():
    spec = MoE(n_experts=8, top_k=2, d_expert=4, n_shared=0,
               scoring="softmax")
    h = jax.random.normal(jax.random.key(0), (5, 6))
    w = jax.random.normal(jax.random.key(1), (6, 8))
    idx, wts = route(h, w, None, spec)
    p = jax.nn.softmax(h @ w, -1)
    top = jnp.argsort(-p, -1)[:, :2]
    assert (jnp.sort(idx, -1) == jnp.sort(top, -1)).all()
    assert jnp.allclose(jnp.sum(wts, -1), 1.0, atol=1e-6)
    raw = MoE(n_experts=8, top_k=2, d_expert=4, n_shared=0,
              scoring="softmax", norm_topk=False)
    _, unnormed = route(h, w, None, raw)
    assert jnp.allclose(unnormed, jnp.take_along_axis(p, idx, -1), atol=1e-6)
    with pytest.raises(ValueError, match="'sigmoid' or 'softmax'"):
        MoE(n_experts=8, top_k=2, d_expert=4, scoring="tanh")


def test_the_shares_of_a_softmax_layer_sum_to_the_uncut_layer():
    """The guide's share test for the new scoring: two modules that hold
    halves of the experts, given their halves of the whole layer's weights,
    give parts that add up to the whole layer's output; no shared expert
    stands beside them."""
    kw = dict(n_experts=8, top_k=2, d_expert=16, n_shared=0,
              scoring="softmax")
    h = jax.random.normal(jax.random.key(2), (2, 6, 32))
    whole = ExpertLayer(MoE(**kw))
    params = whole.init(jax.random.key(3), h)["params"]
    assert set(params) == {"router", "experts_w_gate", "experts_w_up",
                           "experts_w_down"}
    want = whole.apply({"params": params}, h)
    total = 0.0
    for first in (0, 4):
        held = {k: ({"kernel": v["kernel"][first:first + 4]}
                    if k.startswith("experts_") else v)
                for k, v in params.items()}
        part, sown = ExpertLayer(MoE(**kw, held=(first, 4))).apply(
            {"params": held}, h, mutable=[COUNTERS])
        total = total + part
        assert "moe_experts_live" not in sown[COUNTERS]   # no `live` given
        assert "moe_tile_rows" not in sown[COUNTERS]
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5


# ---------------------------------------------------------------- the defaults
def test_the_defaults_build_and_lower_what_they_did():
    sig = ExpertLayer(MoE(n_experts=4, top_k=2, d_expert=8))
    h = jnp.ones((1, 4, 16))
    params = sig.init(jax.random.key(0), h)["params"]
    assert {"e_score_correction_bias", "shared_w_gate", "shared_w_up",
            "shared_w_down"} <= set(params)
    text = lambda f, *a: jax.jit(f).lower(*a).as_text()
    named = lambda f, *a: jax.jit(f).lower(*a).as_text(debug_info=True)
    assert "moe.shared" in named(lambda p, x: sig.apply({"params": p}, x),
                                 params, h)
    soft = ExpertLayer(MoE(n_experts=4, top_k=2, d_expert=8, n_shared=0,
                           scoring="softmax"))
    assert "moe.shared" not in named(
        lambda p, x: soft.apply({"params": p}, x),
        soft.init(jax.random.key(0), h)["params"], h)
    # the module's and the attention's defaults are the causal programs
    tok = jnp.ones((1, 8), jnp.int32)
    plain = TransformerLM(vocab_size=16, d_model=16, n_layers=1, n_heads=2,
                          d_ff=32)
    same = TransformerLM(vocab_size=16, d_model=16, n_layers=1, n_heads=2,
                         d_ff=32, diffusion_block=0, mask_id=None)
    p = plain.init(jax.random.key(0), tok)["params"]
    assert text(lambda p, t: plain.apply({"params": p}, t), p, tok) == text(
        lambda p, t: same.apply({"params": p}, t), p, tok)
    q = jnp.ones((1, 8, 2, 4))
    assert text(lambda a, b, c: dense_causal_attention(a, b, c), q, q,
                q) == text(
        lambda a, b, c: dense_causal_attention(a, b, c, block=0), q, q, q)
    with pytest.raises(ValueError, match="needs `mask_id`"):
        TransformerLM(vocab_size=16, d_model=16, n_layers=1, n_heads=2,
                      d_ff=32, diffusion_block=4).init(jax.random.key(0), tok)


def test_expert_layers_stay_unstacked_and_dense_layers_stack(tiny):
    lm, params, _tokens = tiny
    assert isinstance(decode.stack_blocks(params, lm.n_layers)["blocks"],
                      tuple)
    dense = TransformerLM(vocab_size=16, d_model=16, n_layers=2, n_heads=2,
                          d_ff=32)
    p = dense.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32))["params"]
    assert isinstance(decode.stack_blocks(p, 2)["blocks"], dict)


# --------------------------------------------------------- for the TPU, unrun
@pytest.mark.parametrize("causal", [False, True])
def test_the_grouped_window_kernel_lowers_for_the_tpu_at_the_cells_widths(
        causal):
    """The kernel form the cell runs (16 slots, a window of 4 rows, 32 query
    heads over 4 KV heads of 128, page 16, 2,048-token tables, bfloat16)
    through the Pallas TPU lowering, and through Mosaic itself where libtpu
    can describe a v5e: interpret mode meets neither."""
    S = jax.ShapeDtypeStruct
    slots, pages = 16, 128
    pool = S((slots * pages + 1, 16, 4, 128), jnp.bfloat16)
    args = (S((slots, 4, 32, 128), jnp.bfloat16), pool, pool,
            S((slots, pages), jnp.int32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_))
    fn = lambda q, k, v, pg, po, act: paged_attention(
        q, k, v, pg, po, active=act, interpret=False, causal=causal)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()
    assert 'kernel_name = "paged_attention"' in text
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no TPU compile-only topology: {type(e).__name__}: {e}")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(fn, in_shardings=jax.tree.map(
        lambda _: one, args)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
