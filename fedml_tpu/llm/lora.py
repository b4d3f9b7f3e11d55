"""LoRA adapters as pure pytree transforms.

The reference's FedLLM uses HF peft LoRA on torch modules (reference:
python/spotlight_prj/fedllm/README.md:1). TPU design: no module surgery —
LoRA is a *parameter-space* transform. `lora_init` walks the params pytree
and creates (A, B) factors for every kernel whose path matches the target
filter — 2-D [din, dout], or 3-D [L, din, dout] when the base stacks block
weights (TransformerLM(scan_layers=True)), where the adapters carry the
same leading layer axis; `lora_merge` produces effective weights
W + (alpha/r)·A@B.

What the merge is for: the FORWARD's kernel (one product over W', whatever
the rank, no side path and no pass over the activations added), export to
serving (llm/decode.py, serving/predictor.py), and the model-agnostic
training path `lora_apply_fn` (soak/loop.py, llm/quant.py, the tests'
oracle), where autodiff reaches the adapters through the merge. That
backward forms the MERGED kernel's gradient x^T dy, a [T, din] x [T, dout]
product as large as the forward's, only to project it onto rank r (on the
chip: a fourth pass over the attention projections, 5% of a round; PERF.md
section 6, PR 33). So a model that knows its projections takes the merged
kernel as a CONSTANT of the differentiation and the factors beside it:
`adapted_dot_general` is the product y = x W' whose backward is written out
in rank-r products (TransformerLM's Block multiplies through it;
transformer.adapted_apply_fn is the apply that hands it the two).

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
    Runs inside the jitted step, once a local step and outside any
    rematerialised block."""
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


# The variable collection a model reads its projections' factors from, laid
# out by the adapter pytree's own paths ("blocks/wq/kernel" is
# [LORA]["blocks"]["wq"]["kernel"] = {"a", "b"}), `a` already times alpha/r.
LORA = "lora"


def _slices(m: jax.Array, dtype) -> list:
    """`m` as arrays of `dtype` whose sum is `m`: itself where the dtypes
    agree, else its rounding and the rounding of what that left over (two
    bfloat16 slices carry 16 of a float32's 24 mantissa bits; one carries 8,
    and reads worse than the merged path did)."""
    hi = m.astype(dtype)
    if hi.dtype == m.dtype:
        return [hi]
    return [hi, (m - hi.astype(m.dtype)).astype(dtype)]


def _thin(big: jax.Array, thin: jax.Array, contract: tuple) -> jax.Array:
    """[n, r]: a 2-D operand in the compute dtype times a rank-r float32
    one, contracted over `contract` = (big's axis, thin's; n is big's other
    axis) and accumulated in float32. The thin operand goes in as its slices side
    by side along the rank axis, which the lane width pads anyway, so the
    big operand is read once."""
    parts = _slices(thin, big.dtype)
    out = jax.lax.dot_general(
        big, jnp.concatenate(parts, axis=1 - contract[1]),
        ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32)
    return sum(jnp.split(out, len(parts), axis=1))


@jax.custom_vjp
def _adapted_product(x, w, a, b):
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))


def _adapted_fwd(x, w, a, b):
    return _adapted_product(x, w, a, b), (x, w, a, b)


@jax.jit
def _rank_r_gradients(x, dy, a, b):
    """(da, db) of y = x (W + a b) from dy: four rank-r products, two passes
    over x and two over dy, in the place of x^T dy. The scope is what a
    device trace shows of them. Jitted so that a shape is traced once and
    not every site: under the round's client vmap each jnp call on a
    batched tracer recomputes its aval, which five unrolled layers of four
    sites made a tenth of the sparse cell's set-up (PERF.md section 6)."""
    x = x.reshape(-1, x.shape[-1])
    dy = dy.reshape(-1, dy.shape[-1])
    with jax.named_scope("lm.lora"):
        g = _thin(dy, b, (1, 1))                        # dy B^T   [T, r]
        da = _thin(x, g, (0, 0))                        # x^T g    [din, r]
        u = _thin(x, a, (1, 0))                         # x A      [T, r]
        db = _thin(dy, u, (0, 0)).T                     # u^T dy   [r, dout]
    return da.astype(a.dtype), db.astype(b.dtype)


def _adapted_bwd(res, dy):
    x, w, a, b = res
    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    return (dx, None, *_rank_r_gradients(x, dy, a, b))


_adapted_product.defvjp(_adapted_fwd, _adapted_bwd)


def adapted_dot_general(a: jax.Array, b: jax.Array) -> Callable:
    """The `dot_general` of an `nn.Dense` whose kernel is the MERGED
    W' = W + a b, handed in as a constant of the differentiation: forward
    x W' as `nn.Dense` writes it; backward dx = dy W'^T, da = x^T (dy b^T),
    db = (x a)^T dy, and no cotangent for the kernel, so no [din, dout]
    array is formed. `a` [din, r] and `b` [r, dout] stay float32 whatever
    the compute dtype of x and W'."""

    def dot_general(x, w, dimension_numbers, precision=None,
                    preferred_element_type=None):
        if (dimension_numbers != (((x.ndim - 1,), (0,)), ((), ()))
                or precision is not None
                or preferred_element_type is not None):
            raise NotImplementedError(
                "the adapted product is nn.Dense's default: x's last axis "
                "against the kernel's first")
        return _adapted_product(x, w, a, b)

    return dot_general


def count_params(tree: Pytree) -> int:
    return sum(int(jnp.size(l)) for l in jax.tree.leaves(tree))
