"""Plain reference of the `kexaone_236b_ep8` configuration, for training.

K-EXAONE-236B-A23B's decoder layer as configs/kexaone_236b_ep8.json states
it (the config's keys, what the file lists under `assumed`, and one chip's
share of the 8-way expert-parallel deployment): float32, highest matmul
precision, no kernels, one sequence at a time, a checkpoint per layer.
Imports nothing from fedml_tpu nor from chipbench/models.

  h = RMSNorm(x); q = h Wq [H x dh], k = h Wk, v = h Wv [KV x dh]; an RMSNorm
  over dh on q and on k; rotary positions in a sliding_attention layer only;
  query head i reads KV head i // (H / KV); scores scaled by dh^-0.5, causal,
  and in a sliding_attention layer i sees j only where 0 <= i - j < window;
  x = x + o Wo.
  dense layer: x = x + Wdown(silu(Wgate h') * Wup h'), h' = RMSNorm(x).
  sparse layer: s = sigmoid(h' Wr) over ALL `router_num_experts`; S = the
  `num_experts_per_tok` largest of s + b; g_e = routed_scaling_factor * s_e /
  sum_{e' in S} s_e' (the sum over all chosen, held here or not);
  x = x + sum_{e in S, held here} g_e E_e(h') + E_shared(h').

The held experts are `num_experts` from `expert_share[0] * num_experts` on;
what the absent experts would add is left out, and the logits are over the
vocabulary slice the embedding and the head hold.

The base stays in the dtype it was made in (bfloat16 on the chip: the upcast
is exact) and is upcast a layer, an expert or a 2048-wide slice of the dense
feed-forward at a time, so that the 3.7 B parameters never stand in float32
at once; the scores are computed a query head at a time.

The parameter tree is the unrolled layout the harness hands over:
embed/embedding [V, d]; block_<i>/{RMSNorm_0, RMSNorm_1}/scale [d],
{q_norm, k_norm}/scale [dh], {wq, wk, wv, wo}/kernel; a dense layer's
{w_gate, w_up, w_down}/kernel; a sparse layer's moe/router/kernel [d, E],
moe/e_score_correction_bias [E], moe/experts_{w_gate, w_up, w_down}/kernel
[held, in, out], moe/shared_{w_gate, w_up, w_down}/kernel; final_norm/scale;
lm_head/kernel [d, V]. LoRA adapters: {"block_<i>/<w>/kernel": {"a": [in, r],
"b": [r, out]}}, effective weight W + (alpha/r) A B.

`model["fault"]` plants what chipbench/control.py must see fail:
"drop_expert" leaves the first held expert out of every sparse layer's sum,
"unnormalised" leaves the chosen weights unnormalised. "router_bf16" is no
fault but a probe: the router scores rows rounded to bfloat16, as the program's
are, so tokens whose 8th and 9th scores lie within that noise choose otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, rounder

LORA_ALPHA = 16.0
SLICE = 2048        # columns of a dense feed-forward upcast at a time


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


def swiglu_sum(h, w_gate, w_up, w_down, weight, mm):
    """sum_c weight[:, c] * (silu(h Wgate_c) * (h Wup_c)) Wdown_c over the
    leading axis of the three stacks, one c at a time."""
    @jax.checkpoint
    def one(acc, c):
        wg, wu, wd, g = c
        y = mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)
        return acc + g[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w_gate, w_up, w_down, weight.T))
    return acc


def expert_layer(h, moe, model: dict, mm):
    """(the held experts' part of the routed sum, the shared expert's
    output) of the normed rows h [T, d]."""
    fault = model.get("fault")
    scored = h          # "router_bf16": the rows as bfloat16 holds them
    if fault == "router_bf16":
        scored = h.astype(jnp.bfloat16).astype(jnp.float32)
    s = jax.nn.sigmoid(mm(scored, moe["router"]["kernel"]))      # [T, E]
    _, chosen = jax.lax.top_k(s + _f32(moe["e_score_correction_bias"]),
                              model["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    g = s * picked
    if model["norm_topk_prob"] and fault != "unnormalised":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = model["routed_scaling_factor"] * g
    held = model["num_experts"]
    first = model["expert_share"][0] * held
    g = g[:, first:first + held]    # the absent experts' part is left out
    if fault == "drop_expert":
        g = g.at[:, 0].set(0.0)
    routed = swiglu_sum(
        h, moe["experts_w_gate"]["kernel"], moe["experts_w_up"]["kernel"],
        moe["experts_w_down"]["kernel"], g, mm)
    shared = mm(jax.nn.silu(mm(h, moe["shared_w_gate"]["kernel"]))
                * mm(h, moe["shared_w_up"]["kernel"]),
                moe["shared_w_down"]["kernel"])
    return routed, shared


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None):
    """Logits [T, V] of ONE sequence of token ids [T]."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(_f32(b)), precision=HI)
    n = model["num_hidden_layers"]
    heads, kv_heads = (model["num_attention_heads"],
                       model["num_key_value_heads"])
    dh, eps = model["head_dim"], model["rms_norm_eps"]
    base = float(model["rope_parameters"]["rope_theta"])
    t = tokens.shape[0]
    pos = jnp.arange(t)
    back = pos[:, None] - pos[None, :]          # i - j

    def dense(h, name, layer, lora):
        out = mm(h, layer[name]["kernel"])
        ab = None if lora is None else lora.get(name)
        if ab is not None:
            r = ab["a"].shape[-1]
            out = out + (LORA_ALPHA / r) * mm(mm(h, ab["a"]), ab["b"])
        return out

    def attention(x, layer, lora, windowed):
        h = _rms_norm(x, layer["RMSNorm_0"]["scale"], eps)
        q = dense(h, "wq", layer, lora).reshape(t, heads, dh)
        k = dense(h, "wk", layer, lora).reshape(t, kv_heads, dh)
        v = dense(h, "wv", layer, lora).reshape(t, kv_heads, dh)
        q = _rms_norm(q, layer["q_norm"]["scale"], eps)
        k = _rms_norm(k, layer["k_norm"]["scale"], eps)
        seen = back >= 0
        if windowed:
            q, k = _rope(q, pos, base), _rope(k, pos, base)
            seen = seen & (back < model["sliding_window"])

        @jax.checkpoint
        def one_head(args):
            qh, i = args                        # [T, dh], its head's index
            j = i // (heads // kv_heads)        # the KV head it reads
            kh, vh = k[:, j], v[:, j]
            s = jnp.matmul(rnd(qh), rnd(kh).T, precision=HI) * dh ** -0.5
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.matmul(rnd(p), rnd(vh), precision=HI)

        o = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0), jnp.arange(heads)))
        return x + dense(jnp.moveaxis(o, 0, 1).reshape(t, heads * dh), "wo",
                         layer, lora)

    def dense_ff(x, layer):
        h = _rms_norm(x, layer["RMSNorm_1"]["scale"], eps)
        d, ff = layer["w_gate"]["kernel"].shape
        c = max(1, ff // SLICE)
        cols = lambda w: jnp.moveaxis(w.reshape(d, c, ff // c), 1, 0)
        return x + swiglu_sum(
            h, cols(layer["w_gate"]["kernel"]), cols(layer["w_up"]["kernel"]),
            layer["w_down"]["kernel"].reshape(c, ff // c, d),
            jnp.ones((t, c), jnp.float32), mm)

    def sparse_ff(x, layer):
        h = _rms_norm(x, layer["RMSNorm_1"]["scale"], eps)
        routed, shared = expert_layer(h, layer["moe"], model, mm)
        return x + routed + shared

    x = _f32(params["embed"]["embedding"][tokens])
    for i in range(n):
        windowed = model["layer_types"][i] == "sliding_attention"
        sparse = model["mlp_layer_types"][i] == "sparse"
        lora = None if adapters is None else {
            w: adapters.get(f"block_{i}/{w}/kernel")
            for w in ("wq", "wk", "wv", "wo")}

        def layer_fn(x, layer, lora, windowed=windowed, sparse=sparse):
            x = attention(x, layer, lora, windowed)
            return sparse_ff(x, layer) if sparse else dense_ff(x, layer)

        x = jax.checkpoint(layer_fn)(x, params[f"block_{i}"], lora)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return mm(x, params["lm_head"]["kernel"])
