"""Decoder-only flax transformer for the FedLLM slice.

The reference's FedLLM spotlight fine-tunes LLaMA-style decoders with LoRA
(reference: python/spotlight_prj/fedllm/README.md:1 — README-only in the
snapshot; the model itself comes from HF transformers). Here the model is a
self-contained flax module in the LLaMA shape — RMSNorm, RoPE, causal MHA,
SwiGLU MLP — sized by config so tests run a tiny instance and a real run can
scale it up.

TPU-first details:
- attention is PLUGGABLE (`attn_fn`): the default is dense causal attention;
  under sequence parallelism the caller passes ring_attention/ulysses_attention
  bound to the `seq` mesh axis (parallel/seq.py), with `pos_offset` giving the
  chunk's global position so RoPE angles and causal masks stay correct.
- all matmuls are [B*T, D] x [D, F] shapes that XLA tiles onto the MXU;
  bfloat16 compute composes via models/hub.mixed_precision_apply.
- weights are plain pytrees — LoRA (llm/lora.py) and federated aggregation
  operate on them without touching this module.
"""
from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.seq import dense_causal_attention


def rope(x: jax.Array, pos: jax.Array, base: float = 10000.0) -> jax.Array:
    """Rotary position embedding. x: [B, T, H, D] (D even), pos: [T] global
    token positions."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos[:, None].astype(jnp.float32) * freqs[None, :]   # [T, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class Block(nn.Module):
    n_heads: int
    d_ff: int
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, pos):
        # the layer names a device trace shows (PERF.md section 3): jax
        # writes jvp(...), transpose(jvp(...)) and the checkpoint's
        # rematted_computation around them, so forward, backward and the
        # recomputed forward of each part can be told apart by name
        d_model = x.shape[-1]
        dh = d_model // self.n_heads
        with jax.named_scope("lm.attn"):
            h = RMSNorm()(x)
            q = nn.Dense(d_model, use_bias=False, name="wq")(h)
            k = nn.Dense(d_model, use_bias=False, name="wk")(h)
            v = nn.Dense(d_model, use_bias=False, name="wv")(h)
            split = lambda a: a.reshape(a.shape[:2] + (self.n_heads, dh))
            q, k, v = split(q), split(k), split(v)
            q, k = rope(q, pos), rope(k, pos)
            attn = self.attn_fn or dense_causal_attention
            o = attn(q, k, v)
            o = o.reshape(o.shape[:2] + (d_model,))
            x = x + nn.Dense(d_model, use_bias=False, name="wo")(o)

        with jax.named_scope("lm.mlp"):
            h = RMSNorm()(x)
            gate = nn.Dense(self.d_ff, use_bias=False, name="w_gate")(h)
            up = nn.Dense(self.d_ff, use_bias=False, name="w_up")(h)
            x = x + nn.Dense(d_model, use_bias=False, name="w_down")(
                nn.silu(gate) * up)
        return x


class TransformerLM(nn.Module):
    """LLaMA-shaped causal LM. Input: int tokens [B, T]; output: logits
    [B, T, vocab]. `pos_offset` is the global position of token 0 — nonzero
    when the sequence axis is sharded and this call sees one chunk."""
    vocab_size: int
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 256
    attn_fn: Optional[Callable] = None
    # gradient checkpointing per block: activations are recomputed in the
    # backward instead of stored, trading ~1 extra forward of FLOPs for
    # O(layers x B x T x D) -> O(B x T x D) activation memory — what lets a
    # >=1B-param base train at T=2048 on one chip (SURVEY §5.7 remat note)
    remat: bool = False
    # scan-over-layers: compile ONE block and lax.scan it, with block params
    # stacked on a leading [n_layers] axis (`blocks/...: [L, ...]`). The HLO
    # is O(1) in depth instead of O(L), and compile time drops ~L-fold
    # (the 16-layer 1.2B round program compiles for a v5e in ~11 s:
    # PERF.md). Combines with `remat` (checkpoint per scanned
    # step = the flax remat_scan pattern). llm/lora.py and llm/quant.py
    # both understand the stacked [L, din, dout] kernel layout.
    scan_layers: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, pos_offset=0):
        pos = pos_offset + jnp.arange(tokens.shape[1])
        with jax.named_scope("lm.embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="embed")(tokens)
        if self.scan_layers:
            block = Block
            if self.remat:
                block = nn.remat(block, prevent_cse=False)
            x, _ = nn.scan(
                lambda mdl, carry, _xs: (mdl(carry, pos), None),
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=self.n_layers,
            )(block(self.n_heads, self.d_ff, self.attn_fn, name="blocks"),
              x, None)
        else:
            block_cls = nn.remat(Block) if self.remat else Block
            for i in range(self.n_layers):
                x = block_cls(self.n_heads, self.d_ff, self.attn_fn,
                              name=f"block_{i}")(x, pos)
        with jax.named_scope("lm.head"):
            x = RMSNorm(name="final_norm")(x)
            return nn.Dense(self.vocab_size, use_bias=False,
                            name="lm_head")(x)
