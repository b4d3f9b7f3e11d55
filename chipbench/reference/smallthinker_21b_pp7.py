"""Plain reference of the `smallthinker_21b_pp7` configuration, for serving.

SmallThinker-21BA3B-Instruct's decoder layer as configs/smallthinker_21b_pp7.json
states it: float32, highest matmul precision, no kernel, no cache, no
batching, one sequence at a time. Imports nothing from fedml_tpu nor from
chipbench/models.

For input x to layer l, N = RMSNorm (eps rms_norm_eps):

  h   = N_in(x)
  r   = softmax(h Wr) over all `moe_num_primary_experts`, float32: the
        router reads h, BEFORE attention
  E   = the `moe_num_active_primary_experts` largest of r; p_e = r_e / the
        sum of r over E (`moe_primary_router_apply_softmax`, renormalised)
  x'  = x + Wo Attn_l(h)
  y   = N_post(x')
  out = x' + sum_{e in E} p_e Wd_e(relu(Wg_e y) * (Wu_e y))   (ReGLU)

  Attn_l: q = h Wq [H x dh], k = h Wk, v = h Wv [KV x dh], no norm over a
  head; query head i reads KV head i // (H / KV); scores scaled by dh^-0.5.
  A layer whose `sliding_window_layout` entry is 1 (a WINDOW layer) attends
  the keys i - sliding_window_size + 1 .. i, one whose entry is 0 (a GLOBAL
  layer) the keys 0 .. i; a layer whose `rope_layout` entry is 1 rotates q
  and k (two halves, base rope_theta), one whose entry is 0 takes NO
  rotary positions (the configuration's two lists coincide: the global
  layers are the unrotated ones). A final norm before the untied head.

Every expert is computed for every token and weighted 0 where the router did
not choose it. Attention runs a block of queries at a time and the head only
over the rows asked for, so that a sequence of 16k positions fits beside the
base. The base stays in the dtype it was made in (bfloat16 on the chip: the
upcast is exact) and is upcast inside jitted stages a layer at a time.

`forward`'s `fault` plants one of the three faults the benchmark's check
names: "full_window" (window layers attend every earlier key),
"rope_global" (global layers rotated too), "router_after" (the router reads
y, the feed-forward's input, not h).

The parameter tree is the harness's: embed/embedding [V, d],
final_norm/scale, lm_head/kernel [d, V], and the layers either unrolled
(`block_<i>`) or as `blocks`, a tuple of the layers' trees or one stacked
tree. A layer: {RMSNorm_0, RMSNorm_1}/scale [d], {wq, wk, wv, wo}/kernel,
moe/router/kernel [d, E], moe/experts_{w_gate, w_up, w_down}/kernel
[E, in, out].
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import HI, rounder

FAULTS = ("full_window", "rope_global", "router_after")
QUERY_BLOCK = 512       # queries a pass of attention
TOKEN_BLOCK = 2048      # rows a pass of the experts


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, pos, base):
    """x [T, H, D], pos [T]: rotate the two halves of every head."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_params(params, i: int):
    """Layer i's parameters, whichever layout the tree is in."""
    if f"block_{i}" in params:
        return params[f"block_{i}"]
    blocks = params["blocks"]
    if isinstance(blocks, (tuple, list)):
        return blocks[i]
    return jax.tree.map(lambda a: a[i], blocks)


def _blocks(x, n: int):
    """x [T, ...] as [T / n, n, ...] (T a multiple of n, else one block)."""
    n = n if x.shape[0] % n == 0 else x.shape[0]
    return x.reshape((-1, n) + x.shape[1:])


def expert_layer(y, route, moe, m: dict, mm):
    """sum over the chosen experts of p_e E_e(y), y [T, d] the normed rows,
    the router reading `route` [T, d]: every expert in turn over all rows,
    weighted 0 where it was not chosen."""
    p = jax.nn.softmax(jnp.matmul(route, _f32(moe["router"]["kernel"]),
                                  precision=HI), axis=-1)            # [T, E]
    _, chosen = jax.lax.top_k(p, m["moe_num_active_primary_experts"])
    w = jnp.zeros_like(p).at[jnp.arange(y.shape[0])[:, None], chosen].set(
        jnp.take_along_axis(p, chosen, axis=-1))
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def rows(args):
        yb, wb = args

        def one(acc, e):
            gate, up, down, g = e
            out = mm(jax.nn.relu(mm(yb, gate)) * mm(yb, up), down)
            return acc + g[:, None] * out, None

        out, _ = jax.lax.scan(one, jnp.zeros_like(yb), (
            moe["experts_w_gate"]["kernel"], moe["experts_w_up"]["kernel"],
            moe["experts_w_down"]["kernel"], wb.T))
        return out

    return jax.lax.map(rows, (_blocks(y, TOKEN_BLOCK),
                              _blocks(w, TOKEN_BLOCK))).reshape(y.shape)


@functools.lru_cache(maxsize=None)
def _stages(model_json: str, precision: str, fault) -> dict:
    """The jitted pieces of one forward, for one model, precision and
    planted fault."""
    m = json.loads(model_json)
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(_f32(w)), precision=HI)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    dh, eps = m["head_dim"], m["rms_norm_eps"]
    base, span = float(m["rope_theta"]), m["sliding_window_size"]

    def layer(bl, x, windowed: bool, rotated: bool):
        t = x.shape[0]
        pos = jnp.arange(t)
        h = _rms_norm(x, bl["RMSNorm_0"]["scale"], eps)
        q = mm(h, bl["wq"]["kernel"]).reshape(t, heads, dh)
        k = mm(h, bl["wk"]["kernel"]).reshape(t, kv, dh)
        v = mm(h, bl["wv"]["kernel"]).reshape(t, kv, dh)
        if rotated or fault == "rope_global":
            q, k = _rope(q, pos, base), _rope(k, pos, base)
        k, v = rnd(k), rnd(v)

        def attend(args):
            qb, qpos = args                              # [n, H, dh], [n]
            qg = rnd(qb).reshape(qb.shape[0], kv, heads // kv, dh)
            s = jnp.einsum("qkgd,skd->kgqs", qg, k,
                           precision=HI) * dh ** -0.5
            rel = qpos[:, None] - pos[None, :]
            seen = rel >= 0
            if windowed and fault != "full_window":
                seen = seen & (rel < span)
            a = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), -1)
            return jnp.einsum("kgqs,skd->qkgd", rnd(a), v, precision=HI)

        o = jax.lax.map(attend, (_blocks(q, QUERY_BLOCK),
                                 _blocks(pos, QUERY_BLOCK)))
        x = x + mm(o.reshape(t, heads * dh), bl["wo"]["kernel"])
        y = _rms_norm(x, bl["RMSNorm_1"]["scale"], eps)
        return x + expert_layer(y, y if fault == "router_after" else h,
                                bl["moe"], m, mm)

    @jax.jit
    def head(x, scale, kernel):
        return mm(_rms_norm(x, scale, eps), kernel)

    return {"layer": jax.jit(layer, static_argnames=("windowed", "rotated")),
            "head": head}


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None, rows=None, fault=None):
    """Logits [T, V] of ONE sequence of token ids [T] (position t's logits
    predict token t + 1); `rows` keeps only those positions' logits (the
    head over 16k positions of 151,936 would not fit). `fault` is one of
    FAULTS, or None."""
    if adapters:
        raise NotImplementedError("the serving reference takes no adapters")
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    st = _stages(json.dumps(model, sort_keys=True), precision, fault)
    x = _f32(jnp.take(params["embed"]["embedding"], jnp.asarray(tokens),
                      axis=0))
    for i, (w, r) in enumerate(zip(model["sliding_window_layout"],
                                   model["rope_layout"])):
        x = st["layer"](layer_params(params, i), x, windowed=bool(w),
                        rotated=bool(r))
    if rows is not None:
        x = x[jnp.asarray(np.asarray(rows))]
    return st["head"](x, params["final_norm"]["scale"],
                      params["lm_head"]["kernel"])
