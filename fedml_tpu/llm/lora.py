"""LoRA adapters as pure pytree transforms.

The reference's FedLLM uses HF peft LoRA on torch modules (reference:
python/spotlight_prj/fedllm/README.md:1). TPU design: no module surgery —
LoRA is a *parameter-space* transform. `lora_init` walks the params pytree
and creates (A, B) factors for every kernel whose path matches the target
filter — 2-D [din, dout], or 3-D [L, din, dout] when the base stacks block
weights (TransformerLM(scan_layers=True)), where the adapters carry the
same leading layer axis; `lora_merge` produces effective weights W + (alpha/r)·A@B
inside the traced step, so autodiff w.r.t. the adapters flows through the
merge while the base stays a constant. XLA fuses the rank-r update into the
consuming matmul's epilogue — no runtime module wrapper needed.

Federated consequence (the whole point of the FedLLM slice): clients train
and exchange ONLY the adapter pytree — for the tiny test model that is ~1-2%
of base size; for LLaMA-7B with r=8 it is ~0.06% — so the round payload and
the psum both shrink by that factor while base weights stay replicated.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

Pytree = Any


def _paths_and_leaves(params: Pytree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return flat, treedef


def _path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def lora_init(rng: jax.Array, params: Pytree, rank: int = 8,
              targets: Sequence[str] = ("wq", "wk", "wv", "wo"),
              a_std: float = 0.01) -> dict:
    """Create the adapter pytree: {path_str: {"a": [din, r], "b": [r, dout]}}
    for every `kernel` leaf whose path contains one of `targets`.
    B is zero-initialized (standard LoRA: the merged model starts exactly at
    the base model); A is small-normal. Scan-over-layers bases
    (TransformerLM(scan_layers=True)) stack block kernels [L, din, dout];
    their adapters get the same leading axis ([L, din, r] / [L, r, dout]) —
    a per-layer adapter pair, matmul-broadcast through the merge."""
    flat, _ = _paths_and_leaves(params)
    adapters = {}
    keys = jax.random.split(rng, max(1, len(flat)))
    for i, (path, leaf) in enumerate(flat):
        ps = _path_str(path)
        if leaf.ndim in (2, 3) and ps.endswith("kernel") and any(
                t in ps for t in targets):
            *stack, din, dout = leaf.shape
            adapters[ps] = {
                "a": a_std * jax.random.normal(
                    keys[i], (*stack, din, rank), jnp.float32),
                "b": jnp.zeros((*stack, rank, dout), jnp.float32),
            }
    if not adapters:
        raise ValueError(
            f"no kernels matched LoRA targets {list(targets)}; available: "
            f"{[_path_str(p) for p, l in flat if l.ndim in (2, 3)][:10]}")
    return adapters


def lora_merge(base_params: Pytree, adapters: dict, alpha: float = 16.0,
               ) -> Pytree:
    """Effective weights: W + (alpha/r)·A@B on adapted leaves, base elsewhere.
    Runs inside the jitted step — XLA sees a rank-r matmul fused into the
    consumer."""
    if not adapters:
        return base_params
    rank = next(iter(adapters.values()))["a"].shape[-1]
    scale = alpha / rank

    flat, treedef = jax.tree_util.tree_flatten_with_path(base_params)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        ab = adapters.get(ps)
        if ab is not None:
            leaf = leaf + scale * (ab["a"] @ ab["b"]).astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def lora_apply_fn(apply_fn: Callable, base_params: Pytree,
                  alpha: float = 16.0) -> Callable:
    """Wrap a flax apply into the (adapters -> logits) view the FL engine
    trains: variables = {"params": adapters}. `base_params` may be traced
    values (llm.federated_lora binds the base off the round's broadcast so
    it stays a program argument); concrete arrays closed over here become
    literals of whatever jit traces the result — fine at test sizes, not
    at a billion parameters."""

    def wrapped(variables, x, *args, **kwargs):
        merged = lora_merge(base_params, variables["params"], alpha)
        return apply_fn({"params": merged}, x, *args, **kwargs)

    return wrapped


def count_params(tree: Pytree) -> int:
    return sum(int(jnp.size(l)) for l in jax.tree.leaves(tree))
