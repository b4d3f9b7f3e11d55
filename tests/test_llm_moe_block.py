"""The block beyond the dense one (llm/transformer.py, llm/moe.py,
ops/flash_attention.py): grouped KV heads, window layers, the expert layer
held a share at a time, and what refuses to serve them. Tiny sizes, CPU,
kernels interpreted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import decode
from fedml_tpu.llm.moe import (ExpertLayer, MoE, grouped_matmul, row_tile,
                               tile_rows)
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.ops.flash_attention import flash_attention, flash_attn_fn
from fedml_tpu.parallel.seq import dense_causal_attention

KINDS = (("window", "dense"), ("window", "moe"), ("full", "moe"))
MOE = MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4), scale=2.5)


def kexaone_like(**kw):
    return TransformerLM(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=64,
        n_kv_heads=2, head_dim=16, norm_eps=1e-5, rope_base=1e6,
        rope_full=False, window=8, qk_norm=True, moe=MOE, layer_kinds=KINDS,
        **kw)


# --------------------------------------------------------------- attention
@pytest.mark.parametrize("t,h,kv,d,window,block", [
    (64, 4, 2, 16, None, 16),       # grouped heads, full causal
    (64, 4, 2, 16, 8, 8),           # grouped heads, window = block
    (64, 4, 1, 16, 20, 16),         # one KV head, window over two blocks
    (64, 2, 2, 16, 5, 8),           # a window inside one block
    (32, 4, 4, 8, None, None),      # the dense block's call
], ids=["gqa_full", "gqa_window", "mqa_window_wide", "mha_window_narrow",
        "mha_full"])
def test_flash_forward_and_gradients_match_masked_dense_attention(
        t, h, kv, d, window, block):
    ks = jax.random.split(jax.random.key(t + h + kv), 4)
    q = jax.random.normal(ks[0], (2, t, h, d))
    k = jax.random.normal(ks[1], (2, t, kv, d))
    v = jax.random.normal(ks[2], (2, t, kv, d))
    do = jax.random.normal(ks[3], (2, t, h, d))

    def flash(q, k, v):
        fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(-1, t, d)
        o = flash_attention(fold(q), fold(k), fold(v), block_q=block,
                            block_k=block, window=window, interpret=True)
        return jnp.moveaxis(o.reshape(2, h, t, d), 1, 2)

    dense = lambda q, k, v: dense_causal_attention(q, k, v, window=window)
    o1, vjp1 = jax.vjp(flash, q, k, v)
    o2, vjp2 = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(o1, o2, atol=5e-6)
    for a, b in zip(vjp1(do), vjp2(do)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_window_sees_itself_and_the_positions_before_it_only():
    # position i sees j where 0 <= i - j < window: moving a key outside
    # every window but the last row's own changes that row alone
    q = jax.random.normal(jax.random.key(0), (1, 16, 1, 8))
    k = jax.random.normal(jax.random.key(1), (1, 16, 1, 8))
    v = jax.random.normal(jax.random.key(2), (1, 16, 1, 8))
    base = flash_attn_fn(q, k, v, window=4)
    moved = flash_attn_fn(q, k.at[0, 5].add(1.0), v, window=4)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2, 3))
                             > 1e-7)
    assert changed.tolist() == [5, 6, 7, 8]


def test_unequal_blocks_are_refused_for_a_window():
    x = jnp.zeros((2, 32, 8))
    with pytest.raises(ValueError, match="equal blocks"):
        flash_attention(x, x, x, block_q=16, block_k=8, window=4,
                        interpret=True)
    with pytest.raises(ValueError, match="cannot share"):
        flash_attention(jnp.zeros((3, 32, 8)), x, x, interpret=True)


# --------------------------------------------------------------- the model
def test_the_defaults_are_the_dense_block_parameter_for_parameter():
    tok = jnp.zeros((1, 8), jnp.int32)
    lm = TransformerLM(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                       d_ff=64, scan_layers=True)
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(
        lambda: lm.init(jax.random.key(0), tok)["params"]))
    assert shapes == {
        "embed": {"embedding": (64, 32)}, "final_norm": {"scale": (32,)},
        "lm_head": {"kernel": (32, 64)},
        "blocks": {"RMSNorm_0": {"scale": (2, 32)},
                   "RMSNorm_1": {"scale": (2, 32)},
                   **{w: {"kernel": (2, 32, 32)}
                      for w in ("wq", "wk", "wv", "wo")},
                   "w_gate": {"kernel": (2, 32, 64)},
                   "w_up": {"kernel": (2, 32, 64)},
                   "w_down": {"kernel": (2, 64, 32)}}}
    assert not lm.has_counters and decode.unserved(lm) == []


def test_layer_kinds_give_each_layer_its_parameters_and_its_counters():
    tok = jnp.arange(32).reshape(1, 32) % 50
    lm = kexaone_like(remat=True)
    variables = lm.init(jax.random.key(0), tok)
    p = variables["params"]
    assert set(p["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "q_norm", "k_norm",
                                 "wq", "wk", "wv", "wo", "w_gate", "w_up",
                                 "w_down"}
    assert p["block_0"]["wq"]["kernel"].shape == (32, 64)
    assert p["block_0"]["wk"]["kernel"].shape == (32, 32)
    assert p["block_0"]["wo"]["kernel"].shape == (64, 32)
    moe = p["block_1"]["moe"]
    assert "w_gate" not in p["block_1"]
    assert moe["router"]["kernel"].shape == (32, 8)         # all 8 experts
    assert moe["experts_w_gate"]["kernel"].shape == (4, 32, 16)   # 4 held
    assert moe["experts_w_down"]["kernel"].shape == (4, 16, 32)
    assert moe["e_score_correction_bias"].shape == (8,)
    # the flash path and the dense fallback agree through the whole model
    out, sown = lm.apply({"params": p}, tok, mutable=["counters"])
    out2 = kexaone_like(attn_fn=flash_attn_fn).apply({"params": p}, tok)
    np.testing.assert_allclose(out, out2, atol=2e-5)
    counted = sown["counters"]
    assert set(counted) == {"block_1", "block_2"}
    pairs = int(counted["block_1"]["moe"]["moe_pairs"][0])
    assert 0 < pairs <= 32 * 2
    assert lm.has_counters


def test_scan_layers_refuses_layers_of_different_kinds():
    tok = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="leave the layers unrolled"):
        kexaone_like(scan_layers=True).init(jax.random.key(0), tok)
    with pytest.raises(ValueError, match="layer_kinds must give 3"):
        TransformerLM(vocab_size=8, n_layers=3,
                      layer_kinds=(("full", "dense"),)).kinds


# --------------------------------------------------------- the expert layer
def dense_experts(h, p, spec, held):
    """The expert layer by hand: every held expert on every token, masked
    by the router's choice."""
    s = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, idx = jax.lax.top_k(s + p["e_score_correction_bias"], spec.top_k)
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(1)
    g = spec.scale * s * picked / jnp.sum(s * picked, -1, keepdims=True)
    out = 0.0
    for j, e in enumerate(range(held[0], held[0] + held[1])):
        y = (jax.nn.silu(h @ p["experts_w_gate"]["kernel"][j])
             * (h @ p["experts_w_up"]["kernel"][j])
             ) @ p["experts_w_down"]["kernel"][j]
        out = out + g[:, e:e + 1] * y
    shared = (jax.nn.silu(h @ p["shared_w_gate"]["kernel"])
              * (h @ p["shared_w_up"]["kernel"])) @ p["shared_w_down"]["kernel"]
    return out, shared


def expert_params(key, d=32, f=16, n_all=8, n_held=4):
    ks = jax.random.split(key, 8)
    n = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    return {"router": {"kernel": n(ks[0], d, n_all)},
            "e_score_correction_bias": 0.1 * jax.random.normal(ks[1], (n_all,)),
            "experts_w_gate": {"kernel": n(ks[2], n_held, d, f)},
            "experts_w_up": {"kernel": n(ks[3], n_held, d, f)},
            "experts_w_down": {"kernel": n(ks[4], n_held, f, d)},
            "shared_w_gate": {"kernel": n(ks[5], d, f)},
            "shared_w_up": {"kernel": n(ks[6], d, f)},
            "shared_w_down": {"kernel": n(ks[7], f, d)}}


@pytest.mark.parametrize("held", [(0, 4), (4, 4)], ids=["share0", "share1"])
def test_a_share_computes_its_own_experts_part_and_its_gradient(held):
    spec = MoE(n_experts=8, top_k=2, d_expert=16, held=held, scale=2.5)
    p = expert_params(jax.random.key(1))
    h = jax.random.normal(jax.random.key(2), (1, 24, 32))
    layer = ExpertLayer(spec)

    def program(h, p):
        return layer.apply({"params": p}, h)

    def by_hand(h, p):
        routed, shared = dense_experts(h[0], p, spec, held)
        return (routed + shared)[None]

    np.testing.assert_allclose(program(h, p), by_hand(h, p), atol=2e-5)
    g1 = jax.grad(lambda h: jnp.sum(jnp.sin(program(h, p))))(h)
    g2 = jax.grad(lambda h: jnp.sum(jnp.sin(by_hand(h, p))))(h)
    np.testing.assert_allclose(g1, g2, atol=5e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_held_experts():
    spec = MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4), scale=2.5)
    p = expert_params(jax.random.key(3))
    # the selection bias sends EVERY token to experts 1 and 2, both held
    p["e_score_correction_bias"] = jnp.zeros(8).at[jnp.array([1, 2])].set(9.0)
    h = jax.random.normal(jax.random.key(4), (2, 16, 32))
    out, sown = ExpertLayer(spec).apply({"params": p}, h,
                                        mutable=["counters"])
    routed, shared = dense_experts(h.reshape(-1, 32), p, spec, (0, 4))
    np.testing.assert_allclose(out.reshape(-1, 32), routed + shared,
                               atol=2e-5)
    assert int(sown["counters"]["moe_pairs"][0]) == 2 * 16 * 2   # all of them
    assert int(sown["counters"]["moe_max_rows"][0]) == 2 * 16
    # the bound engages nowhere: the dispatch walked the whole buffer
    assert int(sown["counters"]["moe_rows_walked"][0]) == 2 * 16 * 2


def test_a_share_that_no_token_picks_gives_the_shared_expert_alone():
    spec = MoE(n_experts=8, top_k=2, d_expert=16, held=(4, 4), scale=2.5)
    p = expert_params(jax.random.key(5))
    p["e_score_correction_bias"] = jnp.zeros(8).at[jnp.array([1, 2])].set(9.0)
    h = jax.random.normal(jax.random.key(6), (1, 16, 32))
    out, sown = ExpertLayer(spec).apply({"params": p}, h,
                                        mutable=["counters"])
    _, shared = dense_experts(h[0], p, spec, (4, 4))
    np.testing.assert_allclose(out[0], shared, atol=2e-5)
    assert int(sown["counters"]["moe_pairs"][0]) == 0
    assert int(sown["counters"]["moe_rows_walked"][0]) == 0


# ------------------------------------------ the grouped product's row tile
@pytest.mark.parametrize("p,n_experts,tile", [
    (16 * 4 * 8, 128, 128),     # SDAR's block forward: 4 rows an expert
    (256 * 8, 128, 128),        # SDAR's admission chunk: 16
    (512 * 8, 256, 128),        # GLM-5's admission chunk: 16
    (16 * 1 * 8, 256, 128),     # GLM-5's decode step: the whole buffer
    (8192 * 8, 128, 512),       # K-EXAONE's silo: 512, the ceiling
    (4096 * 8, 16, 512),        # 2,048 an expert: capped at the ceiling
    (24, 8, 24),                # a tiny buffer is its own tile
    (384, 2, 128),              # 192 -> 256, halved until it divides 384
    (640, 1, 128),              # 640 -> 512, halved to 128
], ids=["sdar_block", "sdar_chunk", "glm5_chunk", "glm5_step",
        "kexaone_silo", "ceiling", "tiny", "divisor", "divisor_capped"])
def test_the_row_tile_is_the_rows_an_expert_can_expect(p, n_experts, tile):
    assert row_tile(p, n_experts) == tile
    assert p % tile == 0


UNEVEN = [0, 300, 3, 0, 130, 1, 0, 200]     # empty groups, one over 3 tiles


def test_tile_rows_counts_the_tiles_megablox_visits():
    import importlib
    # the package's `gmm` attribute is the function: ask for the module
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    sizes = jnp.asarray(UNEVEN, jnp.int32)
    # by hand at 128: 3 + 1 + 2 (303..433) + 1 + 2 (434..634) tiles
    assert int(tile_rows(sizes, 128)) == 9 * 128
    assert int(tile_rows(sizes, 512)) == (1 + 1 + 1 + 1 + 2) * 512
    for tm in (128, 256, 512):
        _meta, visited = backend.make_group_metadata(
            group_sizes=sizes, m=1024, tm=tm, start_group=0,
            num_nonzero_groups=len(UNEVEN), visit_empty_groups=False)
        assert int(tile_rows(sizes, tm)) == tm * int(visited)
    assert int(tile_rows(jnp.zeros(4, jnp.int32), 128)) == 0


def test_the_chosen_tile_gives_the_512_tile_product_bit_for_bit():
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    sizes = jnp.asarray(UNEVEN, jnp.int32)
    n = int(sizes.sum())
    rows = jax.random.normal(jax.random.key(0), (1024, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (8, 128, 256), jnp.bfloat16)
    assert row_tile(1024, 64) == 128
    got = grouped_matmul(rows, w, sizes, 64)
    want = megablox.gmm(rows, w, sizes, rows.dtype, (512, 128, 256),
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got[:n], np.float32),
                                  np.asarray(want[:n], np.float32))
    ends = np.cumsum(UNEVEN)
    for g, (lo, hi) in enumerate(zip(ends - UNEVEN, ends)):
        np.testing.assert_allclose(
            np.asarray(got[lo:hi], np.float32),
            np.asarray(rows[lo:hi].astype(jnp.float32)
                       @ w[g].astype(jnp.float32)), rtol=2e-2, atol=0.3)


def test_moe_tile_rows_is_sown_over_live_rows_only_and_counts_by_hand():
    spec = MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4), scale=2.5)
    p = expert_params(jax.random.key(7))
    # every token picks experts 1 and 2, both held
    p["e_score_correction_bias"] = jnp.zeros(8).at[jnp.array([1, 2])].set(9.0)
    h = jax.random.normal(jax.random.key(8), (2, 64, 32))
    live = jnp.arange(128).reshape(2, 64) < 100
    layer = ExpertLayer(spec)
    _out, sown = layer.apply({"params": p}, h, live, mutable=["counters"])
    c = sown["counters"]
    # 256 rows over a router 8 wide: 32 an expert, so the floor of 128;
    # expert 1 holds rows 0..99 (tile 0), expert 2 rows 100..199 (tiles 0, 1)
    assert row_tile(2 * 64 * 2, 8) == 128
    assert int(c["moe_pairs"][0]) == 200
    assert int(c["moe_experts_live"][0]) == 2
    assert int(c["moe_tile_rows"][0]) == 3 * 128
    _out, idle = layer.apply({"params": p}, h, jnp.zeros_like(live),
                             mutable=["counters"])
    assert int(idle["counters"]["moe_tile_rows"][0]) == 0
    _out, training = layer.apply({"params": p}, h, mutable=["counters"])
    assert "moe_tile_rows" not in training["counters"]


# ------------------------------------------- federated LoRA over the block
def test_federated_lora_adapts_unequal_widths_and_reports_the_experts_counts():
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import federated_lora
    from fedml_tpu.parallel.round import build_round_fn

    lm = kexaone_like(attn_fn=flash_attn_fn, remat=True)
    tok = jnp.arange(2 * 16).reshape(2, 1, 16) % 50
    base = lm.init(jax.random.key(0), tok[0])["params"]
    targs = TrainArgs(epochs=1, batch_size=1, learning_rate=0.1,
                      compute_dtype="float32")
    alg, adapters = federated_lora(lm, base, targs, jax.random.key(1), rank=2)
    assert adapters["block_0/wq/kernel"]["a"].shape == (32, 2)
    assert adapters["block_0/wq/kernel"]["b"].shape == (2, 64)
    assert adapters["block_2/wk/kernel"]["b"].shape == (2, 32)
    assert adapters["block_1/wo/kernel"]["a"].shape == (64, 2)
    assert not any("moe" in k or "w_gate" in k for k in adapters)
    data = {"x": tok, "y": tok, "mask": jnp.ones((2, 1))}
    out = build_round_fn(alg, mesh=None)(
        alg.server_init(adapters, None), jnp.zeros((2,)), data, jnp.arange(2),
        jnp.ones((2,)), jax.random.key(2), None)
    m = out.metrics
    assert np.isfinite(m["train_loss"])
    # two sparse layers, two silos of 16 tokens, top 2: at most 128 pairs
    assert 0 < float(m["moe_pairs"]) <= 2 * 2 * 16 * 2
    assert 0 < float(m["moe_max_rows"]) <= 2 * 16 * 2


def test_a_model_that_counts_nothing_keeps_the_rounds_three_metrics():
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import federated_lora
    from fedml_tpu.parallel.round import build_round_fn

    lm = TransformerLM(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                       d_ff=64, scan_layers=True)
    tok = jnp.arange(2 * 8).reshape(2, 1, 8) % 50
    base = lm.init(jax.random.key(0), tok[0])["params"]
    alg, adapters = federated_lora(
        lm, base, TrainArgs(epochs=1, batch_size=1), jax.random.key(1), rank=2)
    out = build_round_fn(alg, mesh=None)(
        alg.server_init(adapters, None), jnp.zeros((2,)),
        {"x": tok, "y": tok, "mask": jnp.ones((2, 1))}, jnp.arange(2),
        jnp.ones((2,)), jax.random.key(2), None)
    assert set(out.metrics) == {"train_loss", "train_acc", "n_samples"}


# ------------------------------------------------------------------ serving
def test_the_decode_path_refuses_each_mechanism_it_lacks_by_name():
    # grouped KV heads, per-head q/k norms, experts, window layers and full
    # layers without rotary positions are all served by the engine; the
    # per-request pair says by name what it cannot
    assert decode.unserved(kexaone_like()) == []
    decode.require_servable(kexaone_like())
    assert decode.engine_only(kexaone_like()) == (
        "window layers or full layers without rotary positions; grouped KV "
        "heads, per-head q/k norms or experts")
    gqa = TransformerLM(vocab_size=8, d_model=32, n_heads=4, n_kv_heads=2)
    assert decode.unserved(gqa) == []
    assert decode.engine_only(gqa).startswith("grouped KV heads")


def test_serving_entry_points_refuse_the_model_before_building_anything():
    from fedml_tpu.serving.engine import DecodeEngine
    from fedml_tpu.serving.predictor import GreedyLMPredictor
    from fedml_tpu.serving.scheduler import start_replica

    lm = kexaone_like()
    with pytest.raises(NotImplementedError, match="rotary positions"):
        GreedyLMPredictor(lm, {})
    # the engine serves the model, but not with shared prefix pages
    with pytest.raises(NotImplementedError, match="window layers"):
        DecodeEngine(lm, {}, n_slots=2, max_len=16)
    # the recipe carries `rope_full`; the per-request path
    # refuses the model by the mechanism it lacks
    with pytest.raises(NotImplementedError, match="full layers without "
                       "rotary positions.*served by the decode engine only"):
        start_replica({"model_kind": "lm", "params": {}, "lm": {
            "vocab_size": 8, "d_model": 32, "n_layers": 1, "n_heads": 4,
            "d_ff": 64, "n_kv_heads": 2, "rope_full": False}})
    # layers of two kinds are not stacked: a tuple of the layers as they are
    tok = jnp.zeros((1, 8), jnp.int32)
    params = lm.init(jax.random.key(0), tok)["params"]
    layers = decode.stack_blocks(params, 3)["blocks"]
    assert isinstance(layers, tuple) and len(layers) == 3


def test_the_sequence_parallel_round_refuses_what_it_would_silently_drop():
    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import make_fedllm_seq_round

    with pytest.raises(NotImplementedError, match="grouped KV heads"):
        make_fedllm_seq_round(kexaone_like(), {}, TrainArgs(), mesh=None)
    with pytest.raises(NotImplementedError, match="window layers"):
        make_fedllm_seq_round(kexaone_like(), {}, TrainArgs(), mesh=None)
