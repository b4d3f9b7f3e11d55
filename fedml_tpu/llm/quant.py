"""Int8 weight quantization for frozen LoRA bases (the QLoRA shape).

BASELINE workload 5 is federated LoRA over LLaMA-2-7B; a bf16 7B base is
14 GB — over half a 16 GB v5e HBM before activations. Since federated LoRA
never updates the base (clients exchange adapters only — llm/lora.py), the
base can be STORED int8 (≈7 GB) and dequantized to bf16 on the fly inside
the jitted step. Each dequantized weight is consumed by exactly one block,
so XLA's buffer liveness keeps only ~one block's bf16 weights resident at a
time; with per-block remat the backward pass re-dequantizes instead of
saving. Peak HBM ≈ int8 base + one block bf16 + activation checkpoints.

Scheme: symmetric per-output-channel int8 (scale = max|w| / 127 over all
axes but the last). Small/1-D leaves (norm scales, biases) stay bf16 — they
are HBM-negligible and precision-critical. This is a storage format, not a
compute format: matmuls still run bf16 on the MXU (int8 matmul would change
numerics; the MXU win here is memory, which is the actual 7B bottleneck).

MEMORY CAVEAT — layout matters: the per-block-liveness argument above holds
for the UNROLLED layer layout, and for the in-scan form below. The
MODULE-level scan path (TransformerLM(scan_layers=True) applied to a
dequantized tree, e.g. lora_apply_fn_quant / scale.build_scaled_fedllm)
materializes the dequantized+merged stack as lax.scan operands — peak HBM
is then int8 base PLUS the dense merged stack (on TP meshes both are
tp-sharded, so per-chip cost is (int8 + merged)/|tp|). The form that keeps
single-block liveness UNDER scan is `make_inscan_quant_apply` below: it
dequantizes + LoRA-merges one layer slice inside the scanned body, which is
what lets the full 7B shape both compile (O(1)-in-depth HLO) and fit one
16 GB v5e (measured: 6.74B at 0.699 MFU — see bench_fedllm_7b).

No reference equivalent — the reference's FedLLM (spotlight_prj/fedllm)
inherits HF peft/bitsandbytes for this; on TPU the transform is ~60 lines
of pytree surgery.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

_MIN_QUANT_SIZE = 4096   # leaves smaller than this stay bf16


_QUANT_SUFFIXES = ("kernel", "embedding")


def _quantizable(path_names, leaf) -> bool:
    """Quantize only actual matmul weights — leaves whose path ends with
    `kernel` or `embedding`. A dimension heuristic cannot tell a stacked
    norm-scale tree [L, D] from a kernel once L is large (a 70B shape has
    80 layers), and norm scales must stay bf16: they are precision-critical,
    HBM-negligible, and an int8 {q,s} with a layer-reduced scale would also
    break the in-scan leading-axis contract."""
    return (path_names and path_names[-1] in _QUANT_SUFFIXES
            and leaf.ndim >= 2 and leaf.size >= _MIN_QUANT_SIZE
            and jnp.issubdtype(leaf.dtype, jnp.floating))


def quantize_tree_int8(params: Pytree) -> Pytree:
    """Replace kernel/embedding float leaves with {"q": int8, "s": f32
    scales}. Structure is preserved; dequantize_tree inverts."""

    def one(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        if not _quantizable(names, leaf):
            return jnp.asarray(leaf, jnp.bfloat16) if jnp.issubdtype(
                leaf.dtype, jnp.floating) else leaf
        w = leaf.astype(jnp.float32)
        # per-out-channel scales: reduce all axes but the last — except for
        # 3-D stacked scan-layer kernels [L, din, dout], which keep their
        # leading layer axis so every layer gets its own channel scales
        red = (1,) if w.ndim == 3 else tuple(range(w.ndim - 1))
        s = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        return {"q": q, "s": s}

    return jax.tree_util.tree_map_with_path(one, params)


def _is_q(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q", "s"}


def dequant_leaf(leaf, dtype=jnp.bfloat16):
    if _is_q(leaf):
        return (leaf["q"].astype(jnp.float32) * leaf["s"]).astype(dtype)
    # bf16 passthrough leaves also cast, so the dequantized tree has ONE
    # uniform dtype — a mixed bf16/f32 tree flips the layer-scan carry
    # dtype mid-loop and lax.scan rejects it
    return leaf.astype(dtype) if jnp.issubdtype(
        jnp.asarray(leaf).dtype, jnp.floating) else leaf


def dequantize_tree(qparams: Pytree, dtype=jnp.bfloat16) -> Pytree:
    """bf16 view of a quantized tree (inside jit: XLA fuses the dequant into
    each consumer and frees per-block buffers after use)."""
    return jax.tree.map(lambda l: dequant_leaf(l, dtype), qparams,
                        is_leaf=_is_q)


def quant_bytes(qparams: Pytree) -> int:
    """Actual storage footprint of the quantized tree (the HBM-budget
    number bench reports)."""
    total = 0
    for leaf in jax.tree.leaves(qparams):
        total += leaf.size * leaf.dtype.itemsize
    return total


def synth_quantized_base(rng: jax.Array, shapes: Pytree) -> Pytree:
    """Random int8 base matching a `jax.eval_shape` tree — for memory and
    throughput probes (bench 7B ceiling) where weight VALUES don't matter
    but the full HBM footprint and matmul shapes must be real. Building
    int8 directly avoids ever materializing the f32/bf16 init (a 7B f32
    init is 28 GB — it could never be quantized after the fact on a 16 GB
    chip). Same quantize/passthrough rule as quantize_tree_int8."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [(path, sd) for path, sd in flat]
    keys = jax.random.split(rng, max(1, len(leaves)))

    def build(i, path, sd):
        names = [str(getattr(p, "key", "")) for p in path]
        if not _quantizable(names, sd):
            return 0.02 * jax.random.normal(keys[i], sd.shape, jnp.bfloat16)
        q = jax.random.randint(keys[i], sd.shape, -127, 128, jnp.int8)
        fan_in = sd.shape[-2] if sd.ndim > 1 else sd.shape[0]
        # scale shapes must MATCH quantize_tree_int8's exactly (3-D stacked
        # kernels keep their leading layer axis: [L, 1, dout]) — the
        # in-scan apply scans the s leaves alongside q
        s_shape = ((sd.shape[0], 1, sd.shape[-1]) if sd.ndim == 3
                   else tuple(1 for _ in sd.shape[:-1]) + sd.shape[-1:])
        s = jnp.full(s_shape, (3.0 / max(fan_in, 1)) ** 0.5 / 127.0,
                     jnp.float32)
        return {"q": q, "s": s}

    return jax.tree_util.tree_unflatten(
        treedef, [build(i, path, sd)
                  for i, (path, sd) in enumerate(leaves)])


# ---- shared functional-forward helpers: the LLaMA block math used by BOTH
# the in-scan training forward below and the KV-cache serving decode
# (llm/decode.py). One implementation, so dequant/LoRA-merge semantics
# cannot drift between training and serving.
def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def split_adapters(adapters, alpha: float):
    """(stacked per-block adapter slices, top-level adapters, rank_scale);
    None/empty adapters -> ({}, {}, 0.0)."""
    if not adapters:
        return {}, {}, 0.0
    rank = next(iter(adapters.values()))["a"].shape[-1]
    blk = {k[len("blocks/"):]: v for k, v in adapters.items()
           if k.startswith("blocks/")}
    top = {k: v for k, v in adapters.items()
           if not k.startswith("blocks/")}
    return blk, top, alpha / rank


def merged_kernel(block, ad_l, name, rank_scale, dtype=jnp.bfloat16):
    """Dequantized (or passthrough) kernel with its LoRA delta merged."""
    w = dequant_leaf(block[name]["kernel"], dtype)
    a = ad_l.get(f"{name}/kernel") if ad_l else None
    if a is not None:
        w = w + rank_scale * (a["a"] @ a["b"]).astype(w.dtype)
    return w


QKV = ("wq", "wk", "wv")


def fused_qkv(block, ad_l, rank_scale, h, width_q: int,
              dtype=jnp.bfloat16):
    """q [B, T, width_q] and k, v [B, T, n] of `h` through ONE product with
    the laid-out kernel `wqkv` [d, G, C] (llm/decode.py `lay_out_blocks`):
    G column groups, group g holding its share of each projection's
    columns, `[q_g | k_g | v_g]`. LoRA deltas of wq/wk/wv merge into their
    own columns, value for value as `merged_kernel` merges them."""
    w = dequant_leaf(block["wqkv"]["kernel"], dtype)
    d, g, c = w.shape
    nq = width_q // g
    widths = (nq, (c - nq) // 2, (c - nq) // 2)
    ads = [ad_l.get(f"{n}/kernel") if ad_l else None for n in QKV]
    if any(a is not None for a in ads):
        w = w + rank_scale * jnp.concatenate([
            jnp.zeros((d, g, n), w.dtype) if a is None else
            (a["a"] @ a["b"]).astype(w.dtype).reshape(d, g, n)
            for a, n in zip(ads, widths)], axis=-1)
    y = (h @ w.reshape(d, g * c)).reshape(h.shape[:-1] + (g, c))
    out, at = [], 0
    for n in widths:
        out.append(y[..., at:at + n].reshape(h.shape[:-1] + (g * n,)))
        at += n
    return out


def project_qkv(block, ad_l, rank_scale, h, n_heads: int,
                dtype=jnp.bfloat16, head_dim=None):
    """Pre-norm hidden -> per-head q [B, T, H, Dh] and k/v [B, T, KV, Dh]
    (RoPE is applied by the caller, whose position semantics differ between
    train and decode). Heads are `head_dim` wide (d_model / n_heads when
    None); wk and wv split into as many heads of it as their width holds.
    A block laid out for serving holds the three as one kernel, `wqkv`."""
    dh = head_dim or h.shape[-1] // n_heads
    split = lambda a: a.reshape(a.shape[:2] + (a.shape[-1] // dh, dh))
    if "wqkv" in block:
        q, k, v = fused_qkv(block, ad_l, rank_scale, h, n_heads * dh, dtype)
        return split(q), split(k), split(v)
    q = h @ merged_kernel(block, ad_l, "wq", rank_scale, dtype)
    k = h @ merged_kernel(block, ad_l, "wk", rank_scale, dtype)
    v = h @ merged_kernel(block, ad_l, "wv", rank_scale, dtype)
    return split(q), split(k), split(v)


def swiglu_mlp(block, ad_l, rank_scale, x, dtype=jnp.bfloat16,
               eps: float = 1e-6):
    h = rms_norm(x, dequant_leaf(block["RMSNorm_1"]["scale"], dtype), eps)
    gate = h @ merged_kernel(block, ad_l, "w_gate", rank_scale, dtype)
    up = h @ merged_kernel(block, ad_l, "w_up", rank_scale, dtype)
    return x + (jax.nn.silu(gate) * up) @ merged_kernel(
        block, ad_l, "w_down", rank_scale, dtype)


def lm_head_logits(params, top_ads, rank_scale, x, dtype=jnp.bfloat16,
                   eps: float = 1e-6):
    x = rms_norm(x, dequant_leaf(params["final_norm"]["scale"], dtype), eps)
    head = dequant_leaf(params["lm_head"]["kernel"], dtype)
    a = top_ads.get("lm_head/kernel") if top_ads else None
    if a is not None:
        head = head + rank_scale * (a["a"] @ a["b"]).astype(head.dtype)
    return x @ head


def make_inscan_quant_apply(n_heads: int, attn_fn=None, alpha: float = 16.0,
                            remat: bool = True, dtype=jnp.bfloat16,
                            eps: float = 1e-6):
    """Forward for a scan-layers TransformerLM whose base stays int8 INSIDE
    the layer scan — the memory-preserving form of the scan+quant combo
    (see MEMORY CAVEAT above): each scan step receives one layer's q/s
    slices and its LoRA slice, dequantizes + merges just that block, uses
    it, and lets XLA free it. Peak HBM ≈ int8 base + ONE dense block +
    remat checkpoints, at O(1)-in-depth HLO — what lets a full 7B-shape
    step both compile and fit on one 16 GB chip.

    Functional mirror of transformer.Block (RMSNorm → RoPE causal MHA →
    RMSNorm → SwiGLU; kernels bias-free) — the parity test pins the two
    implementations together (tests/test_fedllm_scale.py).

    Returns apply(qparams, adapters, tokens, pos_offset=0) -> logits, where
    qparams is quantize_tree_int8 of a TransformerLM(scan_layers=True) init
    and adapters is llm.lora.lora_init of the same (stacked [L, ...] a/b).
    Gradients w.r.t. adapters flow through the scan (per-layer slices are
    scanned inputs).

    Ring-attention composition (the long-context 7B layout): pass
    `attn_fn` bound to a seq mesh axis. Two verified forms:
    - INSIDE a shard_map over (silos, seq): attn_fn =
      functools.partial(parallel.seq.ring_attention, axis_name="seq") with
      pos_offset = axis_index("seq") * T_local, so RoPE angles and the
      causal mask use global positions (make_fedllm_seq_round
      inscan_quant=True does this wiring);
    - under a GSPMD jit: attn_fn = scale.make_ring_attn_fn(mesh, ...) — a
      shard_map ISLAND per scan step; tokens stay global so the default
      pos_offset=0 is correct. The hand-written lax.scan body sidesteps
      the flax nn.scan broadcast-constant limitation that forbids
      scan_layers x seq in the module-level path (scale.py).
    """
    from ..parallel.seq import dense_causal_attention
    from .transformer import rope

    attn = attn_fn or dense_causal_attention

    def apply(qparams, adapters, tokens, pos_offset=0):
        blk_ads, top_ads, rank_scale = split_adapters(adapters, alpha)
        emb = dequant_leaf(qparams["embed"]["embedding"], dtype)
        x = emb[tokens]
        pos = pos_offset + jnp.arange(tokens.shape[1])

        def body(x, layer):
            bl, ad_l = layer
            d_model = x.shape[-1]
            h = rms_norm(x, dequant_leaf(bl["RMSNorm_0"]["scale"], dtype),
                         eps)
            q, k, v = project_qkv(bl, ad_l, rank_scale, h, n_heads, dtype)
            q, k = rope(q, pos), rope(k, pos)
            o = attn(q, k, v).reshape(x.shape[:2] + (d_model,))
            x = x + o @ merged_kernel(bl, ad_l, "wo", rank_scale, dtype)
            x = swiglu_mlp(bl, ad_l, rank_scale, x, dtype, eps)
            return x, None

        if remat:
            # prevent_cse=False: CSE barriers are unnecessary under scan
            # and inhibit fusion (same setting as transformer.py's
            # nn.remat(Block, prevent_cse=False) — the flax remat_scan
            # pattern this function mirrors)
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, (qparams["blocks"], blk_ads))
        return lm_head_logits(qparams, top_ads, rank_scale, x, dtype, eps)

    return apply


def lora_apply_fn_quant(apply_fn, qbase: Pytree, alpha: float = 16.0):
    """lora.lora_apply_fn over an int8 base: dequantize + merge adapters
    inside the traced step. Gradients flow only to the adapters (the
    dequantized base is a constant w.r.t. them)."""
    from .lora import lora_merge

    def wrapped(variables, x, *args, **kwargs):
        base = dequantize_tree(qbase)
        merged = lora_merge(base, variables["params"], alpha)
        return apply_fn({"params": merged}, x, *args, **kwargs)

    return wrapped
