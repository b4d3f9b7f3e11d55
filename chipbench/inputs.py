"""Weights and data from `--seed`, made on the device in one jitted call each.

The program receives these as its inputs; the reference is given the same
arrays (or the same call again, once the program's copies are freed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape, dtype, gain: float):
    """One parameter by the role its name states: `kernel` and `embedding`
    are normal with variance gain/fan_in (fan_in = every axis but the last;
    a stacked [L, in, out] kernel's is `in`), `scale` sits near 1, the LoRA
    factors `a` and `b` have std 0.02, and every
    other leaf (biases) near 0, so that no leaf's gradient is trivially 0."""
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("kernel", "embedding"):
        fan_in = (1 if name == "embedding" else shape[-2] if len(shape) == 3
                  else math.prod(shape[:-1]))
        z = z * (gain / fan_in) ** 0.5
    elif name in ("a", "b"):       # LoRA factors: A B about a tenth of W
        z = 0.02 * z
    elif name == "scale":
        z = 1.0 + 0.1 * z
    else:
        z = 0.1 * z
    return z.astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_shapes(module, seq: int = 8):
    """The parameter tree's shapes of a flax language model (token ids in,
    logits out), without making a weight."""
    return jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, seq), jnp.int32))["params"])


def init_tree(shapes, seed: int, gain: float, dtype, salt: int = 0):
    """A parameter tree of the given shapes, every leaf from the seed."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    meta = tuple((path_str(p), tuple(s.shape)) for p, s in flat)

    @jax.jit
    def make(key):
        return [_leaf(jax.random.fold_in(key, i), p, s, jnp.dtype(dtype),
                      gain) for i, (p, s) in enumerate(meta)]

    key = jax.random.fold_in(jax.random.key(seed), salt)
    return jax.tree_util.tree_unflatten(treedef, make(key))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _gaussian_classes(key, clients, per_client, shape, classes):
    km, ky, kx = jax.random.split(key, 3)
    means = 1.5 * jax.random.normal(km, (classes,) + shape, jnp.float32)
    y = jax.random.randint(ky, (clients, per_client), 0, classes)
    x = means[y] + jax.random.normal(kx, (clients, per_client) + shape,
                                     jnp.float32)
    return x, y.astype(jnp.int32)


def gaussian_classes(seed: int, clients: int, per_client: int, shape,
                     classes: int):
    """CIFAR-shaped synthetic shards: one Gaussian mean per class plus unit
    noise, labels uniform — every row differs, something can be learned."""
    key = jax.random.fold_in(jax.random.key(seed), 0xDA7A)
    return _gaussian_classes(key, clients, per_client, tuple(shape), classes)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _token_rows(key, clients, seqs, t, vocab):
    rows = jax.random.randint(key, (clients, seqs, t + 1), 0, vocab)
    return rows[..., :-1].astype(jnp.int32), rows[..., 1:].astype(jnp.int32)


def token_rows(seed: int, clients: int, seqs: int, t: int, vocab: int):
    """[clients, seqs, t] token ids and their next-token targets."""
    key = jax.random.fold_in(jax.random.key(seed), 0x70C5)
    return _token_rows(key, clients, seqs, t, vocab)
