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
  operate on them without touching this module. What the Block does know of
  LoRA is the backward of an adapted projection: handed the factors beside
  the merged kernels (`adapted_apply_fn`), its `nn.Dense` sites multiply
  through `lora.adapted_dot_general`; handed none, they are plain.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import traverse_util

from ..parallel.seq import dense_causal_attention
from . import latent as latent_attention
from .latent import Latent
from .lora import LORA, adapted_dot_general, lora_merge
from .moe import ExpertLayer, Kernel, MoE

# a layer's kind: (attention, feed-forward). "full" attention is causal over
# the whole sequence, "window" over the last `window` positions, "latent" is
# latent attention under its indexer's selection (llm/latent.py); the
# feed-forward is the "dense" SwiGLU or the "moe" expert layer (llm/moe.py)
DENSE_LAYER = ("full", "dense")
# the kernels a Block multiplies through `_dense`
_DENSE_SITES = "wq|wk|wv|wo|w_gate|w_up|w_down"


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


class Affine(nn.Module):
    """A norm's parameters under the leaf names `scale` (and `bias`), as a
    dict: the norm itself is the caller's (llm/latent.py's functions take a
    block's parameters, whoever declared them)."""
    n: int
    bias: bool = False

    @nn.compact
    def __call__(self):
        out = {"scale": self.param("scale", nn.initializers.ones, (self.n,))}
        if self.bias:
            out["bias"] = self.param("bias", nn.initializers.zeros, (self.n,))
        return out


def _dense(parent: nn.Module, features: int, name: str) -> nn.Dense:
    """`parent`'s bias-free projection `name`: plain, or, where the `lora`
    collection holds factors for its kernel, with the adapted product's
    backward."""
    ab = parent.variables.get(LORA, {}).get(name, {}).get("kernel")
    return nn.Dense(
        features, use_bias=False, name=name,
        dot_general=ab and adapted_dot_general(ab["a"], ab["b"]))


class Block(nn.Module):
    """The ONE decoder block. Its defaults are the dense block (as many KV
    heads as heads, heads of d_model / n_heads, full causal attention with
    rotary positions, SwiGLU); the further fields say where a layer departs
    from it, and a layer that departs nowhere has today's parameters."""
    n_heads: int
    d_ff: int
    attn_fn: Optional[Callable] = None
    n_kv_heads: Optional[int] = None    # fewer than n_heads: grouped heads
    head_dim: Optional[int] = None      # d_model // n_heads when None
    norm_eps: float = 1e-6
    rope_base: Optional[float] = 10000.0    # None: no rotary positions here
    window: Optional[int] = None        # i sees j only where 0 <= i-j < window
    qk_norm: bool = False               # RMSNorm over each head of q and k
    moe: Optional[MoE] = None           # the expert layer in the SwiGLU's place
    latent: Optional[Latent] = None     # latent attention in q/k/v's place
    diffusion_block: int = 0            # > 0: block-causal mask of that length

    @nn.nowrap
    def latent_params(self, d_model: int) -> dict:
        """The parameters llm/latent.py's functions read, declared here."""
        lat = self.latent
        kernels = {
            "wq_a": (d_model, lat.q_rank),
            "wq_b": (lat.q_rank, self.n_heads * (lat.nope + lat.rope)),
            "wkv_a": (d_model, lat.kv_rank + lat.rope),
            "wkv_b": (lat.kv_rank, self.n_heads * (lat.nope + lat.v_dim)),
            "index_wq": (lat.q_rank, lat.index_heads * lat.index_dim),
            "index_wk": (d_model, lat.index_dim),
            "index_w": (d_model, lat.index_heads)}
        out = {name: {"kernel": Kernel(shape, name=name)()}
               for name, shape in kernels.items()}
        out["q_a_norm"] = Affine(lat.q_rank, name="q_a_norm")()
        out["kv_a_norm"] = Affine(lat.kv_rank, name="kv_a_norm")()
        out["index_k_norm"] = Affine(lat.index_dim, True,
                                     name="index_k_norm")()
        return out

    @nn.nowrap
    def heads_attention(self, h, pos, dh: int, n_kv: int, norm):
        """Attention over per-head keys and values projected from `h`."""
        q = _dense(self, self.n_heads * dh, "wq")(h)
        k = _dense(self, n_kv * dh, "wk")(h)
        v = _dense(self, n_kv * dh, "wv")(h)
        split = lambda a: a.reshape(a.shape[:2] + (-1, dh))
        q, k, v = split(q), split(k), split(v)
        if self.qk_norm:
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        if self.rope_base is not None:
            q, k = (rope(q, pos, self.rope_base),
                    rope(k, pos, self.rope_base))
        attn = self.attn_fn or dense_causal_attention
        more = {} if self.window is None else {"window": self.window}
        if self.diffusion_block:
            more["block"] = self.diffusion_block
        o = attn(q, k, v, **more)
        return o.reshape(o.shape[:2] + (self.n_heads * dh,))

    @nn.compact
    def __call__(self, x, pos):
        # the layer names a device trace shows (PERF.md section 3): jax
        # writes jvp(...), transpose(jvp(...)) and the checkpoint's
        # rematted_computation around them, so forward, backward and the
        # recomputed forward of each part can be told apart by name
        d_model = x.shape[-1]
        dh = self.head_dim or d_model // self.n_heads
        n_kv = self.n_kv_heads or self.n_heads
        norm = functools.partial(RMSNorm, eps=self.norm_eps)
        with jax.named_scope("lm.attn"):
            h = norm()(x)
            # what a router that reads the input norm's output is handed
            route_x = (h if self.moe is not None
                       and self.moe.route_from == "attn" else None)
            if self.latent is not None:
                o = latent_attention.full_attention(
                    self.latent_params(d_model), h, pos[None], self.latent,
                    self.n_heads, self.norm_eps, self.rope_base)
            else:
                o = self.heads_attention(h, pos, dh, n_kv, norm)
            x = x + _dense(self, d_model, "wo")(o)

        with jax.named_scope("lm.mlp"):
            h = norm()(x)
            if self.moe is not None:
                return x + ExpertLayer(self.moe, name="moe")(
                    h, route_x=route_x)
            gate = _dense(self, self.d_ff, "w_gate")(h)
            up = _dense(self, self.d_ff, "w_up")(h)
            x = x + _dense(self, d_model, "w_down")(nn.silu(gate) * up)
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
    # Where the model departs from the dense block (Block's fields; the
    # defaults ARE the dense block, parameter for parameter). `layer_kinds`
    # gives each layer's (attention, feed-forward) kind, DENSE_LAYER for
    # every layer when None: "window" layers see the last `window`
    # positions, "full" layers the whole causal half, with rotary
    # positions only if `rope_full`; "moe" layers hold `moe`'s experts
    # (whose router reads the attention norm's output where the MoE's
    # `route_from` is "attn", its gated unit `act`).
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    norm_eps: float = 1e-6
    rope_base: float = 10000.0
    rope_full: bool = True
    window: Optional[int] = None
    qk_norm: bool = False
    moe: Optional[MoE] = None
    latent: Optional[Latent] = None
    layer_kinds: Optional[tuple] = None
    # generation by diffusion over blocks: with `diffusion_block` = B > 0
    # position i attends j iff j // B <= i // B (causal over blocks, both
    # ways inside one) and a position's logits are over ITS OWN token; the
    # decode engine generates a block at a time from `mask_id` tokens
    # (serving/engine.py `_block_all`). 0: causal, a token a step.
    diffusion_block: int = 0
    mask_id: Optional[int] = None

    @property
    def kinds(self) -> tuple:
        kinds = self.layer_kinds or (DENSE_LAYER,) * self.n_layers
        kinds = tuple(tuple(k) for k in kinds)
        if len(kinds) != self.n_layers or any(
                a not in ("full", "window", "latent")
                or f not in ("dense", "moe") for a, f in kinds):
            raise ValueError(
                f"layer_kinds must give {self.n_layers} (attention, "
                "feed-forward) pairs of full|window|latent and dense|moe, "
                f"got {kinds}")
        return kinds

    @staticmethod
    def adapts(path: str) -> bool:
        """Whether the kernel at `path` is one a Block multiplies itself,
        through `_dense`: handed factors in the `lora` collection, it wants
        its merged kernel as a constant (`adapted_apply_fn`)."""
        return bool(re.fullmatch(
            rf"(blocks|block_\d+)/({_DENSE_SITES})/kernel", path))

    @property
    def has_counters(self) -> bool:
        """Whether a call sows into the `counters` collection (the expert
        layers do: llm/moe.py)."""
        return any(f == "moe" for _, f in self.kinds)

    def block(self, kind, **kw) -> Block:
        attention, ff = kind
        if attention == "window" and not self.window:
            raise ValueError("a window layer needs `window`")
        if ff == "moe" and self.moe is None:
            raise ValueError("a moe layer needs `moe`")
        if attention == "latent" and self.latent is None:
            raise ValueError("a latent layer needs `latent`")
        if self.diffusion_block:
            if attention != "full":
                raise ValueError(
                    "a diffusion block's mask is written for full attention "
                    f"over per-head keys, not {attention!r} layers")
            if self.mask_id is None or not 0 <= self.mask_id < self.vocab_size:
                raise ValueError(
                    "a diffusion model needs `mask_id`, a token of its "
                    f"vocabulary; got {self.mask_id!r}")
            kw["diffusion_block"] = self.diffusion_block
        windowed = attention == "window"
        cls = Block
        if self.remat:
            cls = nn.remat(Block, prevent_cse=not self.scan_layers)
        return cls(
            self.n_heads, self.d_ff, self.attn_fn, self.n_kv_heads,
            self.head_dim, self.norm_eps,
            self.rope_base if windowed or self.rope_full else None,
            self.window if windowed else None, self.qk_norm,
            self.moe if ff == "moe" else None,
            self.latent if attention == "latent" else None, **kw)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pos_offset=0):
        pos = pos_offset + jnp.arange(tokens.shape[1])
        kinds = self.kinds
        with jax.named_scope("lm.embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="embed")(tokens)
        if self.scan_layers:
            if len(set(kinds)) > 1:
                raise ValueError(
                    "scan_layers stacks ONE block's parameters over the "
                    f"layers; {sorted(set(kinds))} differ in theirs: leave "
                    "the layers unrolled")
            x, _ = nn.scan(
                lambda mdl, carry, _xs: (mdl(carry, pos), None),
                variable_axes={"params": 0, LORA: 0},
                split_rngs={"params": True},
                length=self.n_layers,
            )(self.block(kinds[0], name="blocks"), x, None)
        else:
            for i, kind in enumerate(kinds):
                x = self.block(kind, name=f"block_{i}")(x, pos)
        with jax.named_scope("lm.head"):
            x = RMSNorm(self.norm_eps, name="final_norm")(x)
            return nn.Dense(self.vocab_size, use_bias=False,
                            name="lm_head")(x)


def adapted_apply_fn(model: nn.Module, base_params, alpha: float = 16.0,
                     compute_dtype: str = "float32",
                     apply_fn: Optional[Callable] = None) -> Callable:
    """`lora.lora_apply_fn` over `model.apply` (or `apply_fn`, a wrapper of
    it): the same (adapters -> logits) view and the same forward, one
    product over each merged kernel, with the merge where it was, once a
    call and outside any rematerialised block. What differs is the backward
    of the projections the model says it multiplies through
    `lora.adapted_dot_general` (`model.adapts(path)`; a TransformerLM's are
    its Blocks' own): their merged kernel goes in as a constant and the
    factors beside it in the `lora` collection, so their gradients are
    rank-r products. An adapter of any other kernel (the head, an expert
    layer's, every kernel of a module that declares none) is differentiated
    through the merge as before. `compute_dtype` is
    `hub.mixed_precision_apply`'s, for the merged parameters and the input
    alone: the factors stay float32."""
    from ..models.hub import mixed_precision_apply

    apply_fn = apply_fn or model.apply
    adapts = getattr(model, "adapts", lambda path: False)

    def wrapped(variables, x, *args, **kwargs):
        adapters = variables["params"]
        sited = {k: v for k, v in adapters.items() if adapts(k)}
        rest = {k: v for k, v in adapters.items() if k not in sited}
        merged = lora_merge(lora_merge(base_params, rest, alpha),
                            jax.lax.stop_gradient(sited), alpha)
        factors = traverse_util.unflatten_dict({
            k: {"a": (alpha / v["a"].shape[-1]) * v["a"], "b": v["b"]}
            for k, v in sited.items()}, sep="/")

        def adapted(vs, x, *args, **kwargs):
            return apply_fn({**vs, LORA: factors}, x, *args, **kwargs)

        return mixed_precision_apply(adapted, compute_dtype)(
            {"params": merged}, x, *args, **kwargs)

    return wrapped
