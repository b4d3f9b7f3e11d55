"""Plain reference of the `olmo1b` configuration, for training and serving.

The block the PROGRAM runs at OLMo-1B's sizes (allenai/OLMo-1B-hf: hidden
2048, 16 layers, 16 heads of 128, no grouped heads, SwiGLU width 8192,
vocab 50,304, RoPE base 10,000), with the two departures the configuration
file lists: RMSNorm with a learned scale where OLMo has a LayerNorm without
parameters, and an untied output head where OLMo ties it. Pre-norm, rotary
positions (half-split rotation), causal softmax attention scaled by
1/sqrt(head), SwiGLU. float32, highest matmul precision, one sequence at a
time, a checkpoint per layer. Imports nothing from fedml_tpu.

The parameter tree is the stacked-layer layout the harness hands over:
embed/embedding [V, d]; blocks/{RMSNorm_0, RMSNorm_1}/scale [L, d];
blocks/{wq, wk, wv, wo}/kernel [L, d, d]; blocks/{w_gate, w_up}/kernel
[L, d, ff]; blocks/w_down/kernel [L, ff, d]; final_norm/scale [d];
lm_head/kernel [d, V]. LoRA adapters: {"blocks/<w>/kernel": {"a": [L, d,
r], "b": [L, r, d]}}, effective weight W + (alpha/r) A B.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, rounder

NORM_EPS = 1e-6
ROPE_BASE = 10000.0
LORA_ALPHA = 16.0


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * scale


def _rope(x, pos):
    """x [T, H, D], pos [T]: rotate the two halves of every head."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None):
    """Logits [T, V] of ONE sequence of token ids [T]."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HI)
    n_heads = model["num_attention_heads"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]

    def dense(h, name, layer, lora):
        out = mm(h, layer[name]["kernel"])
        ab = None if lora is None else lora.get(f"blocks/{name}/kernel")
        if ab is not None:
            r = ab["a"].shape[-1]
            out = out + (LORA_ALPHA / r) * mm(mm(h, ab["a"]), ab["b"])
        return out

    def block(x, layer_and_lora):
        layer, lora = layer_and_lora
        h = _rms_norm(x, layer["RMSNorm_0"]["scale"])
        split = lambda a: a.reshape(t, n_heads, -1)
        q = _rope(split(dense(h, "wq", layer, lora)), pos)
        k = _rope(split(dense(h, "wk", layer, lora)), pos)
        v = split(dense(h, "wv", layer, lora))
        s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=HI)
        s = jnp.where(causal[None], s * q.shape[-1] ** -0.5, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", rnd(p), rnd(v), precision=HI)
        x = x + dense(o.reshape(t, -1), "wo", layer, lora)
        h = _rms_norm(x, layer["RMSNorm_1"]["scale"])
        gate = dense(h, "w_gate", layer, lora)
        up = dense(h, "w_up", layer, lora)
        return x + dense(jax.nn.silu(gate) * up, "w_down", layer, lora), None

    x = params["embed"]["embedding"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(block), x,
                        (params["blocks"], adapters))
    x = _rms_norm(x, params["final_norm"]["scale"])
    return mm(x, params["lm_head"]["kernel"])
