"""FedLLM at scale: TP x LoRA x ring attention x remat, composed under one
jit (BASELINE.md workload 5 — LLaMA-class federated LoRA; reference:
python/spotlight_prj/fedllm/README.md:1 runs HF+peft+deepspeed, which has no
TPU meaning).

The composition is GSPMD-first (SURVEY §5.7):
- the FROZEN base is TP-sharded with the Megatron layout (llm/tp.py specs)
  — a base bigger than one chip's HBM lives spread over the `tp` axis;
- LoRA adapters stay REPLICATED — they are the federated round payload and
  the only trained state (llm/lora.py);
- the batch shards over `dp`, the sequence over `seq`: attention runs as
  ring attention via a shard_map ISLAND inside the jit (parallel/seq.py
  ppermute ring over `seq`; dp/tp ride along as batch-like axes). RoPE is
  applied on the global view before the island, so no pos_offset plumbing;
- per-block gradient checkpointing (TransformerLM(remat=True)) bounds
  activation memory to O(B x T x D) regardless of depth.

Sharded base checkpointing: save_base_sharded/restore_base_sharded write the
TP-sharded base through orbax — each host stores its shards, and restore
targets the SAME mesh layout, so a multi-chip base never funnels through one
host's RAM.

THREE verified program layouts (each parity/dryrun-tested —
tests/test_fedllm_scale.py, __graft_entry__.py):
1. unrolled blocks + ring attention (scan_layers=False, seq axis) — the
   long-context layout for models whose unrolled HLO compiles;
2. scan-layers + TP + dp (scan_layers=True, seq_axis=None) — the deep-model
   layout; O(1)-in-depth HLO, attention per-chip;
3. scan-layers + int8 base + ring attention (scan_layers=True,
   quantize_base=True, seq axis) — the long-context DEEP layout: quant.
   make_inscan_quant_apply's hand-written lax.scan dequantizes one layer
   per step and carries the attention island, which flax nn.scan's
   broadcast-constant tracing cannot (the layout the 7B-across-silos-at-
   long-T north star needs; BASELINE.md workload 5).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.seq import ring_attention
from .lora import lora_init
from .tp import tp_param_specs
from .transformer import adapted_apply_fn

Pytree = Any


def make_ring_attn_fn(mesh: Mesh, seq_axis: str = "seq",
                      dp_axis: Optional[str] = "dp",
                      tp_axis: Optional[str] = "tp"):
    """attn_fn for TransformerLM: ring attention over `seq_axis` as a
    shard_map island inside the surrounding GSPMD jit. q/k/v arrive as
    GLOBAL [B, T, H, D] arrays (RoPE already applied globally); the island
    re-shards them (B over dp, T over seq, H over tp), rotates K/V around
    the seq ring, and hands the global result back to GSPMD. Pass
    dp_axis/tp_axis=None to leave that dimension unsharded (e.g. a
    (silos, seq) federated mesh uses dp_axis='silos', tp_axis=None); an
    axis NAME that is not in the mesh is an error, not a silent
    replication — a quietly-dropped dp axis would make every seq ring
    group redundantly attend over the GLOBAL batch."""
    for what, ax in (("seq_axis", seq_axis), ("dp_axis", dp_axis),
                     ("tp_axis", tp_axis)):
        if ax is not None and ax not in mesh.axis_names:
            raise ValueError(
                f"{what}={ax!r} is not an axis of mesh {mesh.axis_names}; "
                f"pass {what}=None to leave that dimension unsharded")
    spec = P(dp_axis, seq_axis, tp_axis, None)

    ring = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def attn(q, k, v):
        return ring(q, k, v)

    return attn


def build_scaled_fedllm(model_cls, mesh: Mesh, *, vocab_size: int,
                        d_model: int, n_layers: int, n_heads: int,
                        d_ff: int, rank: int = 8,
                        alpha: float = 16.0, lr: float = 1e-3,
                        seq_axis: Optional[str] = "seq",
                        dp_axis: str = "dp",
                        compute_dtype: str = "bfloat16",
                        scan_layers: bool = False,
                        quantize_base: bool = False,
                        rng: Optional[jax.Array] = None):
    """Construct the full scaled stack: returns (model, base_sharded,
    adapters, step_fn) where step_fn(adapters, tokens, targets) ->
    (adapters, loss) trains ONLY the adapters against the TP-sharded frozen
    base with ring attention + remat under one jit.

    Two extra knobs complete the 7B-pod composition:
    - scan_layers: lax.scan one compiled block over stacked [L, ...] params
      (O(1)-in-depth HLO; deep models whose unrolled program exceeds a
      compile service's limits). LoRA adapters and TP specs follow the
      stacked layout automatically.
    - quantize_base: store the frozen base int8 (llm/quant.py) — ~1 byte/
      param spread over the tp axis, dequantized to compute_dtype inside
      the step (per-chip: int8/|tp| plus the tp-sharded dense merged
      weights; see quant.py's MEMORY CAVEAT for the scan-layout
      materialization details).
    """
    rng = jax.random.key(0) if rng is None else rng
    # a mesh without the seq axis degrades to dense attention AND an
    # unsharded sequence dim — both guards must agree on mesh membership
    has_seq = bool(seq_axis) and seq_axis in mesh.axis_names
    inscan = scan_layers and has_seq
    if inscan and not quantize_base:
        raise ValueError(
            "scan_layers composes with the ring-attention seq axis only "
            "through the int8 in-scan path (quantize_base=True): flax "
            "nn.scan's broadcast-constant tracing rejects a shard_map "
            "island inside the scanned block ('broadcasted variable has a "
            "data dependency on the scan body'), but quant.make_inscan_"
            "quant_apply's hand-written lax.scan accepts one. Pick one: "
            "quantize_base=True (in-scan int8 + ring — the long-context "
            "deep-model layout), seq_axis=None (scan + TP + dp; attention "
            "stays per-chip), or scan_layers=False (unrolled blocks + ring "
            "attention).")
    attn = (make_ring_attn_fn(
        mesh, seq_axis=seq_axis, dp_axis=dp_axis,
        tp_axis="tp" if "tp" in mesh.axis_names else None)
            if has_seq else None)
    # inscan: the flax module is NOT the forward (its nn.scan would reject
    # the attention island) — quant.make_inscan_quant_apply is; the module
    # is still returned for metadata/eval, with per-chip dense attention
    model = model_cls(vocab_size=vocab_size, d_model=d_model,
                      n_layers=n_layers, n_heads=n_heads, d_ff=d_ff,
                      attn_fn=None if inscan else attn, remat=True,
                      scan_layers=scan_layers)
    # init DIRECTLY into the TP layout: jit the initializer with its output
    # shardings set to the Megatron specs, so each device materializes only
    # its own shard — the full base never exists replicated anywhere
    host_model = model_cls(vocab_size=vocab_size, d_model=d_model,
                           n_layers=n_layers, n_heads=n_heads, d_ff=d_ff,
                           remat=True, scan_layers=scan_layers)
    dtype = jnp.dtype(compute_dtype)

    def raw_init(r):
        return host_model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]

    if quantize_base:
        from .quant import dequantize_tree, quantize_tree_int8

        def init_fn(r):
            return quantize_tree_int8(raw_init(r))
    else:
        def init_fn(r):
            return jax.tree.map(lambda a: a.astype(dtype), raw_init(r))

    shape_tree = jax.eval_shape(init_fn, rng)
    specs = tp_param_specs(shape_tree)
    out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    base = jax.jit(init_fn, out_shardings=out_shardings)(rng)
    # adapters need the UNQUANTIZED kernel shapes (lora_init matches on
    # `.../kernel` paths, which a quantized tree nests under {q, s})
    adapters = lora_init(jax.random.fold_in(rng, 1),
                         jax.eval_shape(raw_init, rng), rank=rank)

    batch_spec = NamedSharding(
        mesh, P(dp_axis, seq_axis if has_seq else None))

    if inscan:
        from .quant import make_inscan_quant_apply

        inscan_apply = make_inscan_quant_apply(
            n_heads, attn_fn=attn, alpha=alpha, dtype=dtype)

    # base rides as a jit ARGUMENT: closing over a multi-GB pytree captures
    # it as lowering constants (minutes of extra compile at the 1B scale)
    @jax.jit
    def _step(base, adapters, tokens, targets):
        tokens = jax.lax.with_sharding_constraint(tokens, batch_spec)
        targets = jax.lax.with_sharding_constraint(targets, batch_spec)

        def loss_fn(ad):
            if inscan:
                # int8 base dequantized one layer at a time INSIDE the scan,
                # ring attention as a shard_map island per scan step —
                # tokens stay global, so RoPE's default positions are right
                logits = inscan_apply(base, ad, tokens)
            else:
                dense_base = (dequantize_tree(base, dtype) if quantize_base
                              else base)
                logits = adapted_apply_fn(model, dense_base, alpha)(
                    {"params": ad}, tokens)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
            return -ll.mean()

        loss, grads = jax.value_and_grad(loss_fn)(adapters)
        adapters = jax.tree.map(lambda a, g: a - lr * g, adapters, grads)
        return adapters, loss

    def step(adapters, tokens, targets):
        return _step(base, adapters, tokens, targets)

    return model, base, adapters, step


# ---------------------------------------------------- sharded checkpointing
def save_base_sharded(path: str, base: Pytree) -> None:
    """Orbax save of the TP-sharded base — shards stream from their devices;
    no single-host gather."""
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, {"base": base}, force=True)
    ckptr.wait_until_finished()   # StandardCheckpointer saves async


def restore_base_sharded(path: str, template: Pytree, mesh: Mesh,
                         tp_axis: str = "tp") -> Pytree:
    """Restore the base DIRECTLY into its TP layout: the abstract target
    carries NamedShardings, so orbax places each shard on its device."""
    import orbax.checkpoint as ocp

    specs = tp_param_specs(template, tp_axis)
    abstract = jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            jnp.shape(leaf), jnp.asarray(leaf).dtype,
            sharding=NamedSharding(mesh, s)),
        template, specs)
    out = ocp.StandardCheckpointer().restore(path, {"base": abstract})
    return out["base"]
