"""Plain reference of the `squid_tiny` configuration: pre-norm blocks of
grouped-query causal attention (no positions beyond the mask) and a gated
MLP, one sequence at a time, float32. Imports nothing of fedml_tpu and
nothing of models/squid.py. The parameter tree is flax's: embed/embedding,
block_<i>/{norm_attn, norm_mlp}/scale, block_<i>/{wq, wk, wv, wo, w_gate,
w_up, w_down}/kernel, final_norm/scale, lm_head/kernel; a LoRA adapter is
{"block_<i>/<w>/kernel": {"a", "b"}}, effective weight W + (16 / r) A B."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, rounder


def _norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None):
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HI)
    t = tokens.shape[0]
    kv, dh = model["kv_heads"], model["head_width"]
    group = model["query_heads"] // kv
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"]["embedding"][tokens]
    for i in range(model["num_hidden_layers"]):
        layer = params[f"block_{i}"]

        def dense(h, name):
            out = mm(h, layer[name]["kernel"])
            ab = (adapters or {}).get(f"block_{i}/{name}/kernel")
            if ab is not None:
                out = out + (16.0 / ab["a"].shape[-1]) * mm(
                    mm(h, ab["a"]), ab["b"])
            return out

        h = _norm(x, layer["norm_attn"]["scale"])
        q = dense(h, "wq").reshape(t, kv, group, dh)
        k = dense(h, "wk").reshape(t, kv, dh)
        v = dense(h, "wv").reshape(t, kv, dh)
        s = jnp.einsum("qkgd,skd->kgqs", rnd(q), rnd(k), precision=HI)
        p = jax.nn.softmax(jnp.where(causal, s * dh ** -0.5, -1e30), -1)
        o = jnp.einsum("kgqs,skd->qkgd", rnd(p), rnd(v), precision=HI)
        x = x + dense(o.reshape(t, -1), "wo")
        h = _norm(x, layer["norm_mlp"]["scale"])
        x = x + dense(jax.nn.silu(dense(h, "w_gate")) * dense(h, "w_up"),
                      "w_down")
    x = _norm(x, params["final_norm"]["scale"])
    return mm(x, params["lm_head"]["kernel"])
