"""Latent attention under its indexer's selection (llm/latent.py), on the
training path (llm/transformer.py's Block) and through the decode engine's
latent page pool (llm/decode.py `make_paged_latent_decode`,
serving/engine.py): the two forms of the attention against each other, the
selection against a sort, the programs against the whole-sequence forward
with contexts under and over `index_topk`, and what is still refused, by its
mechanism. (A prefix hit against a miss and the engine's counters: the
file's longest case, in tests/test_latent_decode_engine.py since PR 36 so
that `--dist loadfile` can give it a worker of its own.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import decode
from fedml_tpu.llm import latent as la
from fedml_tpu.llm.latent import Latent
from fedml_tpu.llm.moe import MoE
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.utils import metrics as mx

LAT = Latent(q_rank=24, kv_rank=16, nope=8, rope=4, v_dim=8, index_heads=2,
             index_dim=8, index_topk=8)
MOE = MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4), scale=2.5)
KINDS = (("latent", "dense"), ("latent", "moe"), ("latent", "moe"))
PAGE, MAX_PAGES = 4, 16


def model(**kw):
    return TransformerLM(vocab_size=50, d_model=32, n_layers=3, n_heads=4,
                         d_ff=64, norm_eps=1e-5, rope_base=1e6, latent=LAT,
                         moe=MOE, layer_kinds=KINDS, **kw)


@pytest.fixture(scope="module")
def seeded():
    m = model()
    tokens = jax.random.randint(jax.random.key(1), (1, 40), 1, 50)
    params = m.init(jax.random.key(0), tokens)["params"]
    # norms and the LayerNorm's bias away from their trivial initial values
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.key(len(str(path))), a.shape) if a.ndim == 1 else a,
        params)
    return m, params, tokens


# --------------------------------------------------------------- the pieces
def test_select_top_is_the_stable_sorts_first_k_among_the_valid():
    rs = np.random.RandomState(0)
    scores = rs.randn(3, 5, 40).astype(np.float32)
    scores[0, 0, :10] = 0.5                       # ties at the threshold
    scores[1, 1] = np.round(scores[1, 1]) + 0.0   # ties everywhere
    valid = rs.rand(3, 5, 40) < 0.8
    valid[2, 2] = False
    valid[2, 2, :3] = True                        # fewer valid than k
    got = np.asarray(jax.jit(lambda s, v: la.select_top(s, v, 7, (2,)))(
        jnp.asarray(scores), jnp.asarray(valid)))
    for b in range(3):
        for c in range(5):
            order = sorted(np.nonzero(valid[b, c])[0],
                           key=lambda i: (-scores[b, c, i], i))
            want = np.zeros(40, bool)
            want[order[:7]] = True
            assert (got[b, c] == want).all(), (b, c)
    # over two axes with another between them: the kernels' blocked layout
    blocked = np.round(rs.randn(2, 4, 3, 8)).astype(np.float32) + 0.0
    got = np.asarray(la.select_top(jnp.asarray(blocked),
                                   jnp.ones(blocked.shape, bool), 5, (1, 3)))
    for b in range(2):
        for c in range(3):
            flat = blocked[b, :, c, :].reshape(-1)
            want = np.zeros(32, bool)
            want[np.argsort(-flat, kind="stable")[:5]] = True
            assert (got[b, :, c, :].reshape(-1) == want).all()


def test_rope_pairs_keeps_every_product_of_the_interleaved_rotation():
    rs = np.random.RandomState(1)
    x, y = (jnp.asarray(rs.randn(1, 6, 2, 8), jnp.float32) for _ in range(2))
    pos = jnp.arange(6)[None] * 7

    def interleaved(v):
        half = v.shape[-1] // 2
        ang = pos[..., None, None] * 1e6 ** (-jnp.arange(half) / half)
        a, b = v[..., 0::2], v[..., 1::2]
        return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                          a * jnp.sin(ang) + b * jnp.cos(ang)],
                         -1).reshape(v.shape)

    want = jnp.einsum("bthd,bshd->bhts", interleaved(x), interleaved(y))
    got = jnp.einsum("bthd,bshd->bhts", la.rope_pairs(x, pos, 1e6),
                     la.rope_pairs(y, pos, 1e6))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_absorbed_form_is_the_per_head_form(seeded):
    """q_lat . c_kv + q_rope . k_rope and (sum a c_kv) W_UV against per-head
    keys and values made from c_kv, over one block's projections."""
    m, params, tokens = seeded
    bl = params["block_1"]
    h = jax.random.normal(jax.random.key(3), (1, 12, 32))
    pos = jnp.arange(12)[None]
    c_q, q_nope, q_rope, c_kv, k_rope = la.project(
        bl, h, pos, LAT, 4, 1e-5, 1e6)
    w_uk, w_uv = la.wkv_b_heads(bl, LAT, 4, jnp.float32)
    k_nope = jnp.einsum("bsk,khn->bshn", c_kv, w_uk)
    v = jnp.einsum("bsk,khv->bshv", c_kv, w_uv)
    s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
         + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope)) * LAT.scale
    seen = pos[:, :, None] >= pos[:, None, :]
    a = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
    want = jnp.einsum("bhts,bshv->bthv", a, v).reshape(1, 12, -1)

    qf = la.absorb_queries(bl, q_nope, q_rope, LAT)
    rows = la.cached_row(c_kv, k_rope, LAT)
    assert qf.shape[-1] == rows.shape[-1] == LAT.width == 128
    s2 = jnp.einsum("bthw,bsw->bhts", qf, rows)
    np.testing.assert_allclose(s2, s, atol=1e-5)
    a2 = jax.nn.softmax(jnp.where(seen[:, None], s2, -1e30), -1)
    o_lat = jnp.einsum("bhts,bsk->bthk", a2, rows[..., :LAT.kv_rank])
    np.testing.assert_allclose(la.expand_values(bl, o_lat, LAT), want,
                               atol=1e-5)


# ------------------------------------------------- programs against the model
def _pool(n_slots):
    n = n_slots * MAX_PAGES + 1
    return {"kv": jnp.zeros((3, n, PAGE, LAT.width)),
            "ik": jnp.zeros((3, n, PAGE, LAT.index_dim))}


def test_prefill_then_decode_through_the_pool_is_the_full_forward(seeded):
    """Chunks of 8 up to 24 tokens, then a token a step to 40: every logit
    row against the whole-sequence forward. index_topk is 8, so the first
    chunk selects nothing and every later query does."""
    m, params, tokens = seeded
    full = m.apply({"params": params}, tokens)[0]
    stacked = decode.stack_blocks(params, 3)
    # layers of two kinds stay apart, and nothing is copied
    assert isinstance(stacked["blocks"], tuple) and len(stacked["blocks"]) == 3
    assert stacked["blocks"][1]["wo"]["kernel"] is params["block_1"]["wo"][
        "kernel"]
    chunk, step, _verify, chunk_batch = decode.make_paged_latent_decode(
        m, PAGE)
    cache, row = _pool(2), jnp.arange(1, MAX_PAGES + 1, dtype=jnp.int32)
    jchunk, jstep = jax.jit(chunk), jax.jit(step)
    for t0 in range(0, 24, 8):
        cache, logits = jchunk(stacked, None, cache, row,
                               tokens[:, t0:t0 + 8], t0, 8)
        np.testing.assert_allclose(logits[0], full[t0 + 7], atol=2e-5)
    pages = jnp.zeros((2, MAX_PAGES), jnp.int32).at[1].set(row)
    for t in range(24, 40):
        cache, logits = jstep(
            stacked, None, cache, pages, jnp.array([0, t]),
            jnp.array([0, tokens[0, t]]), jnp.array([False, True]))
        np.testing.assert_allclose(logits[1], full[t], atol=2e-5)
    # a batch of chunks, one row a pad row: the same last logits
    cache2 = _pool(2)
    rows = jnp.stack([row, jnp.zeros_like(row)])
    _, logits = jax.jit(chunk_batch)(
        stacked, None, cache2, rows, jnp.stack([tokens[0, :8], tokens[0, :8]]),
        jnp.zeros((2,), jnp.int32), jnp.array([8, 0]))
    np.testing.assert_allclose(logits[0], full[7], atol=2e-5)


def test_what_is_still_refused_is_refused_by_its_mechanism(seeded):
    from fedml_tpu.parallel import partition
    from fedml_tpu.serving.engine import DecodeEngine
    from fedml_tpu.serving.predictor import GreedyLMPredictor

    m, params, _ = seeded
    assert decode.unserved(m) == []
    with pytest.raises(NotImplementedError, match="a latent row has no heads"):
        DecodeEngine(m, params, n_slots=1, max_len=16, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="mp=2 over latent pages"):
        partition.paged_latent_cache_spec("mp", 2)
    with pytest.raises(NotImplementedError, match="LoRA adapters on a latent"):
        DecodeEngine(m, params, {"blocks/wo/kernel": {}}, n_slots=1,
                     max_len=16)
    with pytest.raises(NotImplementedError, match="decode engine only"):
        GreedyLMPredictor(m, params)
    mixed = TransformerLM(vocab_size=8, d_model=32, n_layers=2, n_heads=4,
                          latent=LAT, layer_kinds=(("latent", "dense"),
                                                   ("full", "dense")))
    assert [s.split(":")[0] for s in decode.unserved(mixed)] == [
        "latent layers beside layers of per-head keys and values"]
    moe_full = TransformerLM(vocab_size=8, d_model=32, n_layers=1, n_heads=4,
                             moe=MOE, layer_kinds=(("full", "moe"),))
    # experts under full attention are served since PR 36, by the engine
    assert decode.unserved(moe_full) == []
    assert decode.engine_only(moe_full).endswith("or experts")
    # the model's own eps and rope base are served now
    assert decode.unserved(TransformerLM(vocab_size=8, d_model=32, n_heads=4,
                                         norm_eps=1e-5, rope_base=5e5)) == []


def test_start_replica_builds_the_model_from_the_recipe(seeded):
    """The `lm` recipe carries the fields the module was made from."""
    from fedml_tpu.serving.scheduler import start_replica

    m, params, _ = seeded
    lm = {"vocab_size": 50, "d_model": 32, "n_layers": 3, "n_heads": 4,
          "d_ff": 64, "norm_eps": 1e-5, "rope_base": 1e6,
          "latent": {f: getattr(LAT, f) for f in LAT.__dataclass_fields__},
          "moe": {"n_experts": 8, "top_k": 2, "d_expert": 16, "held": [0, 4],
                  "scale": 2.5},
          "layer_kinds": [list(k) for k in KINDS]}
    _job, runner = start_replica({
        "model_kind": "lm", "lm": lm, "params": params, "port": 0,
        "serve": {"decode_slots": 2, "engine_max_len": 64, "kv_page_size": 4,
                  "prefill_chunk": 8, "paged_kernel": True}})
    try:
        engine = runner.predictor.engine
        assert engine.model == m
        out = runner.predictor.predict({"tokens": [3, 4, 5, 6, 7],
                                        "max_new_tokens": 3})
        assert len(out["generated_tokens"]) == 3
    finally:
        runner.stop()


def test_top_shows_the_selected_share_only_where_an_indexer_selects():
    from fedml_tpu.__main__ import _top_frame
    from fedml_tpu.utils.prometheus import (
        parse_prometheus, render_prometheus,
    )

    def frame(seen, picked):
        snap = parse_prometheus(render_prometheus(mx.snapshot()))
        for name, value in (("serving_engine_context_keys_total", seen),
                            ("serving_engine_selected_keys_total", picked),
                            ("serving_tokens_total", 1)):
            snap["counters"][name] = value
        return _top_frame(snap, "test")

    assert "selected 25%" in frame(8000, 2000)
    assert "selected" not in frame(8000, 8000)      # the dense block: all
