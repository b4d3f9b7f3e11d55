"""Plain reference of the `glm5_744b_ep16` configuration, for serving.

GLM-5's decoder layer as configs/glm5_744b_ep16.json states it (the config's
keys, what the file lists under `assumed`, and one chip's share of the 16-way
expert-parallel deployment): float32, highest matmul precision, no kernel, no
cache, no absorbed form, one sequence at a time. Imports nothing from
fedml_tpu nor from chipbench/models.

Pre-norm block, N = RMSNorm (eps rms_norm_eps): h = x + Attn(N(x)),
y = h + FFN(N(h)); a final norm before the head.

  latent attention: c_q = N(x W_DQ); q_h = c_q W_UQ,h = q_nope,h || q_rope,h;
  x W_DKV = c || k_r; c_kv = N(c); k_rope = RoPE(k_r), one row for all heads;
  q_rope,h = RoPE(q_rope,h) (interleaved pairs); [k_nope,h || v_h] = c_kv
  W_UKV,h; a_tsh = softmax over s in S_t of (q_nope,h . k_nope,h,s + q_rope,h .
  k_rope,s) / sqrt(nope + rope); o_t = concat_h(sum_s a_tsh v_h,s) W_O.
  indexer: q^I_tj = (c_q W^I_Q)_j, k^I_s = LayerNorm(x_s W^I_K), rotary
  positions on their first `qk_rope_head_dim` dims; w_tj = (x_t W^I_w)_j
  Hi^-1/2 Di^-1/2; I_ts = sum_j w_tj relu(q^I_tj . k^I_s); S_t = the
  `index_topk` positions s <= t of largest I_ts (all while there are no more;
  of equal scores the earlier position first).
  dense layer: SwiGLU. sparse layer: s = sigmoid(h' W_r) over ALL
  `router_num_experts`; the `num_experts_per_tok` largest of s + b chosen;
  g_e = routed_scaling_factor s_e / sum_chosen s; the HELD experts' part of
  sum_e g_e E_e(h') plus the shared expert.

The held experts are `n_routed_experts` from `expert_share[0] *
n_routed_experts` on; what the absent experts would add is left out, and the
logits are over the vocabulary slice the embedding and the head hold.

The base stays in the dtype it was made in (bfloat16 on the chip: the upcast
is exact) and is upcast a layer's kernel, an expert or a group of heads at a
time, so that 3.9 B parameters never stand in float32 at once. A long
sequence is computed in blocks of `BLOCK` queries against all its keys; the
[T, T] selection of a layer is kept as booleans.

The parameter tree is the harness's: embed/embedding [V, d], final_norm/scale,
lm_head/kernel [d, V], and the layers either unrolled (`block_<i>`) or as
`blocks`, one stacked tree or a tuple of the layers' trees. A
layer: {RMSNorm_0, RMSNorm_1, q_a_norm, kv_a_norm}/scale, index_k_norm/{scale,
bias}, {wq_a, wq_b, wkv_a, wkv_b, wo, index_wq, index_wk, index_w}/kernel; a
dense layer's {w_gate, w_up, w_down}/kernel; a sparse layer's
moe/router/kernel [d, E], moe/e_score_correction_bias [E],
moe/experts_{w_gate, w_up, w_down}/kernel [held, in, out],
moe/shared_{w_gate, w_up, w_down}/kernel.

`model["fault"]` plants what the controls must see fail: "selection_ignored"
attends every earlier position; "stale_index" leaves the indexer's key of
every position from `model["stale_from"]` on unwritten (zero), as a cache
whose newest page was not written would read.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, rounder

BLOCK = 256         # queries a block, once a sequence is longer than LONG
LONG = 4096
HEADS = 4           # heads a group


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _rope(x, pos, base):
    """x [T, ..., D], interleaved pairs (2i, 2i + 1) rotated by pos *
    base^(-2i / D)."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freqs
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def layer_params(params, i: int):
    """Layer i's parameters, whichever layout the tree is in."""
    if f"block_{i}" in params:
        return params[f"block_{i}"]
    blocks = params["blocks"]
    if isinstance(blocks, (tuple, list)):
        return blocks[i]
    return jax.tree.map(lambda a: a[i], blocks)


def _mm(rnd):
    return lambda a, w: jnp.matmul(rnd(a), rnd(_f32(w)), precision=HI)


@functools.partial(jax.jit, static_argnames=("precision", "topk", "n"))
def _select_block(qi, w, ki, start, precision, topk, n):
    """Which of the T keys queries start .. start + n - 1 select."""
    rnd = rounder(precision)
    t = ki.shape[0]
    q = jax.lax.dynamic_slice_in_dim(qi, start, n)
    d = jnp.einsum("qhd,sd->qhs", rnd(q), rnd(ki), precision=HI)
    scores = jnp.einsum("qh,qhs->qs", jax.lax.dynamic_slice_in_dim(w, start, n),
                        jnp.maximum(d, 0.0), precision=HI)
    seen = jnp.arange(t)[None, :] <= (start + jnp.arange(n))[:, None]
    if t <= topk:
        return seen
    # the k largest of the scores it may see, the earlier position first
    # among equals. No sort and no top_k (on the chip either compiles for
    # minutes at every new T): the k-th largest is found by halving. A
    # float's bits, flipped below zero, order as the floats do; 32 times,
    # the largest value that at least k scores reach gains its next bit.
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    low = -2 ** 31                              # below every score's bits
    bits = jnp.where(seen, bits, low)
    reach = lambda trial: jnp.sum(bits >= trial, axis=1, keepdims=True) >= topk
    kth = jnp.where(reach(0), 0, low).astype(jnp.int32)     # the sign first
    for bit in range(30, -1, -1):
        kth = jnp.where(reach(kth + 2 ** bit), kth + 2 ** bit, kth)
    above, equal = bits > kth, bits == kth
    left = topk - jnp.sum(above, axis=1, keepdims=True)
    return seen & (above | (equal & (jnp.cumsum(equal, axis=1) <= left)))


@functools.partial(jax.jit, static_argnames=("precision", "n"))
def _attend_block(q_nope, q_rope, k_nope, k_rope, v, sel, start, scale,
                  precision, n):
    """Heads [G] of queries start .. start + n - 1 over all T keys."""
    rnd = rounder(precision)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, n)
    s = (jnp.einsum("qhn,shn->hqs", rnd(cut(q_nope)), rnd(k_nope),
                    precision=HI)
         + jnp.einsum("qhr,sr->hqs", rnd(cut(q_rope)), rnd(k_rope),
                      precision=HI)) * scale
    a = jax.nn.softmax(jnp.where(sel[None], s, -1e30), axis=-1)
    return jnp.einsum("hqs,shv->qhv", rnd(a), rnd(v), precision=HI)


@functools.lru_cache(maxsize=None)
def _stages(model_json: str, precision: str) -> dict:
    """The layer's steps as jitted functions of one model and precision: a
    new sequence length then compiles a dozen programs, where the same
    steps run operation by operation compiled two hundred (four minutes on
    the chip: PERF.md section 6, PR 34)."""
    m = json.loads(model_json)
    rnd = rounder(precision)
    mm = _mm(rnd)
    eps, base = m["rms_norm_eps"], float(m["rope_parameters"]["rope_theta"])
    heads, nope, rope = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"])
    rank = m["kv_lora_rank"]
    hi, di = m["index_n_heads"], m["index_head_dim"]
    stale_from = m.get("stale_from", 0) if m.get("fault") == "stale_index" \
        else None

    def project(bl, x):
        """The residual's norm and everything attention makes of it that
        is not per head: (h, c_q, c_kv, k_rope, q^I, k^I, w)."""
        t = x.shape[0]
        pos = jnp.arange(t)
        h = _rms_norm(x, bl["RMSNorm_0"]["scale"], eps)
        c_q = _rms_norm(mm(h, bl["wq_a"]["kernel"]), bl["q_a_norm"]["scale"],
                        eps)
        kv = mm(h, bl["wkv_a"]["kernel"])
        c_kv = _rms_norm(kv[:, :rank], bl["kv_a_norm"]["scale"], eps)
        k_rope = _rope(kv[:, rank:], pos, base)
        qi = mm(c_q, bl["index_wq"]["kernel"]).reshape(t, hi, di)
        qi = jnp.concatenate(
            [_rope(qi[..., :rope], pos, base), qi[..., rope:]], axis=-1)
        ki = _layer_norm(mm(h, bl["index_wk"]["kernel"]),
                         bl["index_k_norm"]["scale"],
                         bl["index_k_norm"]["bias"], eps)
        ki = jnp.concatenate(
            [_rope(ki[..., :rope], pos, base), ki[..., rope:]], axis=-1)
        if stale_from is not None:
            ki = jnp.where((pos >= stale_from)[:, None], 0.0, ki)
        w = mm(h, bl["index_w"]["kernel"]) * (hi ** -0.5 * di ** -0.5)
        return h, c_q, c_kv, k_rope, qi, ki, w

    def per_head(c_q, c_kv, wq_b, wkv_b):
        """A group of heads' (q_nope, q_rope, k_nope, v) from c_q, c_kv."""
        pos = jnp.arange(c_q.shape[0])
        q = jnp.einsum("tr,rhd->thd", rnd(c_q), rnd(_f32(wq_b)), precision=HI)
        kvh = jnp.einsum("tr,rhd->thd", rnd(c_kv), rnd(_f32(wkv_b)),
                         precision=HI)
        return (q[..., :nope], _rope(q[..., nope:], pos, base),
                kvh[..., :nope], kvh[..., nope:])

    def merge(out, o, wo):
        return out + jnp.einsum("thv,hvd->td", rnd(o), rnd(_f32(wo)),
                                precision=HI)

    def dense_ffn(bl, x):
        """x + SwiGLU(N(x)), a block of rows at a time: the 12,288-wide
        activations of 29,000 rows would not stand in float32 at once."""
        rows = x.reshape(-1, min(BLOCK, x.shape[0]), x.shape[1])
        one = lambda r: r + _swiglu(
            mm, _rms_norm(r, bl["RMSNorm_1"]["scale"], eps),
            bl["w_gate"]["kernel"], bl["w_up"]["kernel"],
            bl["w_down"]["kernel"])
        return jax.lax.map(one, rows).reshape(x.shape)

    def sparse_ffn(scale, moe, x):
        return x + sum(expert_layer(_rms_norm(x, scale, eps), moe, m, rnd))

    def head(x, scale, kernel):
        return mm(_rms_norm(x, scale, eps), kernel)

    return {k: jax.jit(f) for k, f in locals().items()
            if k in ("project", "per_head", "merge", "dense_ffn",
                     "sparse_ffn", "head")}


ATTENTION_LEAVES = ("RMSNorm_0", "wq_a", "q_a_norm", "wkv_a", "kv_a_norm",
                    "index_wq", "index_wk", "index_k_norm", "index_w")


def _attention(bl, x, m, precision, stages):
    """(the layer's normed input [T, d], attention's output [T, d], the
    selection [T, T] bool as a list of blocks of `n` queries)."""
    heads, nope, rope = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"])
    rank, vd = m["kv_lora_rank"], m["v_head_dim"]
    t = x.shape[0]
    n = BLOCK if t > LONG else t
    h, c_q, c_kv, k_rope, qi, ki, w = stages["project"](
        {k: bl[k] for k in ATTENTION_LEAVES}, x)
    topk = t if m.get("fault") == "selection_ignored" else m["index_topk"]
    # the selection, a block of queries at a time and kept so: [T, T]
    # booleans stand once, never twice
    sel = [_select_block(qi, w, ki, jnp.int32(a), precision, topk, n)
           for a in range(0, t, n)]
    del qi, ki, w
    # per-head keys and values from c_kv, a group of heads at a time
    g = min(HEADS, heads)
    wq_b = bl["wq_b"]["kernel"].reshape(-1, heads, nope + rope)
    wkv_b = bl["wkv_b"]["kernel"].reshape(rank, heads, nope + vd)
    wo = bl["wo"]["kernel"].reshape(heads, vd, -1)
    out = jnp.zeros_like(x)
    for h0 in range(0, heads, g):
        q_nope, q_rope, k_nope, v = stages["per_head"](
            c_q, c_kv, wq_b[:, h0:h0 + g], wkv_b[:, h0:h0 + g])
        o = jnp.concatenate([
            _attend_block(q_nope, q_rope, k_nope, k_rope, v, sel[a // n],
                          jnp.int32(a), (nope + rope) ** -0.5, precision, n)
            for a in range(0, t, n)])
        out = stages["merge"](out, o, wo[h0:h0 + g])
    return h, out, sel


def selected_rows(sel: list, lo: int, hi: int):
    """Rows lo .. hi - 1 of a selection kept a block of queries at a time."""
    n = sel[0].shape[0]
    return jnp.concatenate(sel[lo // n:(hi - 1) // n + 1])[
        lo - lo // n * n:][:hi - lo]


def _swiglu(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def expert_layer(h, moe, m, rnd=lambda a: a):
    """(the held experts' part of the routed sum, the shared expert's
    output) of a sparse layer for rows h [T, d]."""
    mm = _mm(rnd)
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(moe["router"]["kernel"]),
                                  precision=HI))
    _, idx = jax.lax.top_k(s + _f32(moe["e_score_correction_bias"]),
                           m["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if m["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = m["routed_scaling_factor"] * g
    first = m["expert_share"][0] * m["n_routed_experts"]

    def one(e, routed):
        weight = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        mine = lambda name: jax.lax.dynamic_index_in_dim(
            moe[name]["kernel"], e, keepdims=False)
        return routed + weight[:, None] * _swiglu(
            mm, h, mine("experts_w_gate"), mine("experts_w_up"),
            mine("experts_w_down"))

    routed = jax.lax.fori_loop(0, m["n_routed_experts"], one,
                               jnp.zeros_like(h))
    return routed, _swiglu(mm, h, moe["shared_w_gate"]["kernel"],
                           moe["shared_w_up"]["kernel"],
                           moe["shared_w_down"]["kernel"])


def router_edge(h, moe, m):
    """How near rows h [R, d] come to routing otherwise: (the distance
    between the last score chosen and the first one not, of s + b; whether
    either of the two is a HELD expert, so that swapping them changes what
    this chip computes)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(moe["router"]["kernel"]),
                                  precision=HI))
    top, idx = jax.lax.top_k(s + _f32(moe["e_score_correction_bias"]), k + 1)
    first = m["expert_share"][0] * m["n_routed_experts"]
    edge = idx[:, k - 1:]
    held = (edge >= first) & (edge < first + m["n_routed_experts"])
    return top[:, k - 1] - top[:, k], jnp.any(held, axis=-1)


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None, rows=None, observe=None, observe_router=None):
    """tokens [T] -> logits [T, V] of ONE sequence, or of its positions
    `rows = (lo, hi)` alone. `observe(i, h, selected)` is called a layer
    with its normed input to attention [T, d] and the keys each query of
    `rows` selects [hi - lo, T] bool; `observe_router(i, margin, held)` a
    sparse layer with `router_edge` of the rows."""
    if adapters:
        raise NotImplementedError("this configuration serves no adapters")
    m = model
    stages = _stages(json.dumps(m, sort_keys=True), precision)
    t = tokens.shape[0]
    lo, hi = rows or (0, t)
    x = _f32(params["embed"]["embedding"][tokens])
    for i in range(m["num_hidden_layers"]):
        bl = layer_params(params, i)
        h, o, sel = _attention(bl, x, m, precision, stages)
        if observe is not None:
            observe(i, h, selected_rows(sel, lo, hi))
        del sel, h
        x = x + o
        if "moe" in bl:
            if observe_router is not None:
                observe_router(i, *router_edge(
                    _rms_norm(x[lo:hi], bl["RMSNorm_1"]["scale"],
                              m["rms_norm_eps"]), bl["moe"], m))
            x = stages["sparse_ffn"](bl["RMSNorm_1"]["scale"], bl["moe"], x)
        else:
            x = stages["dense_ffn"](
                {k: bl[k] for k in ("RMSNorm_1", "w_gate", "w_up", "w_down")},
                x)
    return stages["head"](x[lo:hi], params["final_norm"]["scale"],
                          params["lm_head"]["kernel"])
