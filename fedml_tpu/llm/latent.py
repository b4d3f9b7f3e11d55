"""Latent attention with a learned sparse selection (DeepSeek-V3's
multi-head latent attention under DeepSeek-V3.2's lightning indexer), as
plain functions over one block's parameters.

Queries come through a low-rank bottleneck, `c_q = N(x W_DQ)`, `q_h = c_q
W_UQ,h = q_nope,h || q_rope,h`. Keys and values come from ONE compressed row
a token, `x W_DKV = c || k_r`, `c_kv = N(c)`, `k_rope = RoPE(k_r)` shared by
every head, `[k_nope,h || v_h] = c_kv W_UKV,h`. An indexer scores every
earlier position for every query, `I_ts = sum_j w_tj relu(q^I_tj . k^I_s)`,
and the query attends only the `index_topk` positions of largest score (all
of them while there are no more).

Two forms of the same attention live here, and one set of projections:

- `full_attention`: per-head keys and values from `c_kv`, a dense [T, T]
  mask made from the scores' top-k. What `llm/transformer.py`'s Block runs
  (training, a whole-sequence forward).
- the ABSORBED form the decode programs run (`llm/decode.py`): `q_lat,h =
  q_nope,h W_UK,h^T`, a score is `(q_lat,h . c_kv,s + q_rope,h . k_rope,s) /
  sqrt(nope + rope)`, the output `(sum_s a c_kv,s) W_UV,h`: nothing per head
  is ever stored, a cached token is `c_kv || k_rope` and its index key.
  `absorb_queries` and `expand_values` are its two ends; what lies between
  them is a kernel over pages (`ops/paged_attention.py`).

Rotary positions rotate INTERLEAVED pairs (2i, 2i + 1), in attention and in
the indexer. `rope_pairs` returns the rotated pairs as all first members and
then all second members: q and k alike, so every product of the two is the
interleaved rotation's, and no product reads a rotated vector's order.

Parameters of a block, each a `{"kernel": [in, out]}` but for the norms:
`wq_a`, `q_a_norm` (scale), `wq_b`, `wkv_a`, `kv_a_norm` (scale), `wkv_b`,
`index_wq`, `index_wk`, `index_k_norm` (scale, bias), `index_w`; `wo` is the
Block's own.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .quant import rms_norm

RULED_OUT = -1e30      # a score no softmax gives weight (the kernels' `_NEG`)
_LANES = 128


@dataclasses.dataclass(frozen=True)
class Latent:
    """The sizes of latent attention and of its indexer."""
    q_rank: int             # q_lora_rank
    kv_rank: int            # kv_lora_rank
    nope: int               # qk_nope_head_dim
    rope: int               # qk_rope_head_dim
    v_dim: int              # v_head_dim
    index_heads: int
    index_dim: int
    index_topk: int

    @property
    def width(self) -> int:
        """A cached row's width: `c_kv || k_rope` padded to whole lanes of
        128, which is what the chip stores a row in whatever its shape."""
        return -(-(self.kv_rank + self.rope) // _LANES) * _LANES

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5


def layer_norm(x, scale, bias, eps: float):
    f = x.astype(jnp.float32)
    mu = jnp.mean(f, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(f - mu), axis=-1, keepdims=True)
    return ((f - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale + bias


def rope_pairs(x, pos, base: float):
    """x [B, T, ..., D] (D even), pos [B, T] -> the interleaved pairs of x
    rotated by `pos * base^(-2i / D)`, first members then second members."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos[..., None].astype(jnp.float32) * freqs          # [B, T, half]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _kernel(bl, name, dtype):
    return jnp.asarray(bl[name]["kernel"], dtype)


def project(bl, h, pos, lat: Latent, n_heads: int, eps: float, base: float):
    """h [B, T, d] (normed), pos [B, T] -> (c_q [B, T, q_rank], q_nope
    [B, T, H, nope], q_rope [B, T, H, rope] rotated, c_kv [B, T, kv_rank],
    k_rope [B, T, rope] rotated)."""
    dt = h.dtype
    c_q = rms_norm(h @ _kernel(bl, "wq_a", dt),
                   jnp.asarray(bl["q_a_norm"]["scale"], dt), eps)
    q = (c_q @ _kernel(bl, "wq_b", dt)).reshape(
        h.shape[:2] + (n_heads, lat.nope + lat.rope))
    q_nope, q_rope = q[..., :lat.nope], q[..., lat.nope:]
    kv = h @ _kernel(bl, "wkv_a", dt)
    c_kv = rms_norm(kv[..., :lat.kv_rank],
                    jnp.asarray(bl["kv_a_norm"]["scale"], dt), eps)
    return (c_q, q_nope, rope_pairs(q_rope, pos, base), c_kv,
            rope_pairs(kv[..., lat.kv_rank:], pos, base))


def index_inputs(bl, h, c_q, pos, lat: Latent, eps: float, base: float):
    """The indexer's side of a token: (q^I [B, T, Hi, Di], k^I [B, T, Di],
    w [B, T, Hi] float32). Rotary positions on the first `rope` dims of
    q^I and k^I; the key goes through a LayerNorm; the heads' weights carry
    `Hi^-1/2 Di^-1/2`."""
    dt = h.dtype
    hi, di, r = lat.index_heads, lat.index_dim, lat.rope
    q = (c_q @ _kernel(bl, "index_wq", dt)).reshape(h.shape[:2] + (hi, di))
    q = jnp.concatenate([rope_pairs(q[..., :r], pos, base), q[..., r:]], -1)
    k = layer_norm(h @ _kernel(bl, "index_wk", dt),
                   jnp.asarray(bl["index_k_norm"]["scale"], dt),
                   jnp.asarray(bl["index_k_norm"]["bias"], dt), eps)
    k = jnp.concatenate([rope_pairs(k[..., :r], pos, base), k[..., r:]], -1)
    w = (h @ _kernel(bl, "index_w", dt)).astype(jnp.float32) * (
        hi ** -0.5 * di ** -0.5)
    return q, k, w


def wkv_b_heads(bl, lat: Latent, n_heads: int, dtype):
    """`W_UKV` as ([kv_rank, H, nope], [kv_rank, H, v_dim])."""
    w = _kernel(bl, "wkv_b", dtype).reshape(
        lat.kv_rank, n_heads, lat.nope + lat.v_dim)
    return w[..., :lat.nope], w[..., lat.nope:]


def absorb_queries(bl, q_nope, q_rope, lat: Latent):
    """[B, T, H, width]: `q_nope W_UK^T || q_rope || 0`, scaled: what a
    cached row `c_kv || k_rope || 0` is multiplied by."""
    w_uk, _ = wkv_b_heads(bl, lat, q_nope.shape[2], q_nope.dtype)
    q_lat = jnp.einsum("bthn,khn->bthk", q_nope, w_uk)
    pad = lat.width - lat.kv_rank - lat.rope
    q = jnp.concatenate([q_lat, q_rope], axis=-1) * lat.scale
    return jnp.pad(q, ((0, 0),) * 3 + ((0, pad),)).astype(q_nope.dtype)


def cached_row(c_kv, k_rope, lat: Latent):
    """[B, T, width]: `c_kv || k_rope || 0`."""
    pad = lat.width - lat.kv_rank - lat.rope
    return jnp.pad(jnp.concatenate([c_kv, k_rope], axis=-1),
                   ((0, 0), (0, 0), (0, pad)))


def expand_values(bl, o_lat, lat: Latent):
    """[B, T, H, kv_rank] weighted sums of `c_kv` -> [B, T, H * v_dim]."""
    _, w_uv = wkv_b_heads(bl, lat, o_lat.shape[2], o_lat.dtype)
    o = jnp.einsum("bthk,khv->bthv", o_lat, w_uv)
    return o.reshape(o.shape[:2] + (-1,))


def _ordered(scores):
    """float32 -> uint32 of the same order (a total one: -0.0 below 0.0);
    nothing a float holds maps to 0."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(
        key ^ jnp.int32(-2 ** 31), jnp.uint32)


def select_top(scores, valid, k: int, axes):
    """Which entries are among the `k` largest VALID scores, over `axes`
    (earlier entries first among equals, in the axes' row-major order):
    a bool array of `scores`' shape. Where `k` or fewer are valid, all of
    them. Exact, and no sort: the k-th largest is built bit by bit, each
    bit a compare and a count over the scores."""
    key = jnp.where(valid, _ordered(scores), jnp.uint32(0))
    count = lambda m: jnp.sum(m, axis=axes, keepdims=True, dtype=jnp.int32)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(key >= cand) >= k, cand, kth)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(count(valid).shape, jnp.uint32))
    above, tied = key > kth, key == kth
    # among the ties at the k-th score, the earliest fill what is left
    order, inner = 0, tied.astype(jnp.int32)
    for i, axis in enumerate(sorted(axes, reverse=True)):
        run = jnp.cumsum(inner, axis=axis)
        order = order + (run if i == 0 else run - inner)
        inner = jnp.sum(inner, axis=axis, keepdims=True)
    return valid & (above | (tied & (order <= k - count(above))))


def full_attention(bl, h, pos, lat: Latent, n_heads: int, eps: float,
                   base: float):
    """h [B, T, d] (normed), pos [B, T] -> [B, T, H * v_dim]: causal
    attention of every query over the `index_topk` earlier positions its
    indexer scores highest, per-head keys and values made from `c_kv`."""
    c_q, q_nope, q_rope, c_kv, k_rope = project(
        bl, h, pos, lat, n_heads, eps, base)
    t = h.shape[1]
    seen = pos[:, :, None] >= pos[:, None, :]                     # [B, T, T]
    with jax.named_scope("lm.index"):
        qi, ki, w = index_inputs(bl, h, c_q, pos, lat, eps, base)
        if t > lat.index_topk:
            d = jnp.einsum("bthd,bsd->bths", qi, ki,
                           preferred_element_type=jnp.float32)
            scores = jnp.einsum("bth,bths->bts", w, jnp.maximum(d, 0.0))
            seen = select_top(scores, seen, lat.index_topk, (2,))
    w_uk, w_uv = wkv_b_heads(bl, lat, n_heads, h.dtype)
    k_nope = jnp.einsum("bsk,khn->bshn", c_kv, w_uk)
    v = jnp.einsum("bsk,khv->bshv", c_kv, w_uv)
    s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * lat.scale
    a = jax.nn.softmax(jnp.where(seen[:, None], s, RULED_OUT), axis=-1)
    o = jnp.einsum("bhts,bshv->bthv", a.astype(v.dtype), v)
    return o.reshape(o.shape[:2] + (-1,))
