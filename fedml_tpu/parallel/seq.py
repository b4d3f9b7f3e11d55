"""Sequence/context parallelism primitives: ring attention + Ulysses.

The reference has NO long-context machinery (SURVEY.md §2.8: grep for
ring-attention/ulysses/sequence-parallel over the reference returns nothing) —
its longest-sequence workloads are LSTM LMs. The FedLLM north star
(BASELINE.md workload 5; reference: python/spotlight_prj/fedllm/README.md:1)
needs sequences longer than one chip's HBM, so sequence parallelism is built
here as a first-class mesh axis, per SURVEY §5.7:

- **Ring attention** (`ring_attention`): the sequence is sharded over a `seq`
  mesh axis; each device keeps its Q chunk resident and the K/V chunks rotate
  around the ring via `ppermute` while an online-softmax accumulator merges
  each block — flash-attention's (m, l, o) recurrence distributed over chips.
  Compute overlaps the ICI transfer; memory per chip is O(T/n).
- **Ulysses** (`ulysses_attention`): all_to_all re-shards [B, T/n, H, D] to
  [B, T, H/n, D], runs ordinary dense attention per head group, and
  all_to_alls back. Cheaper when heads >= devices and T fits per-chip.

Both are numerically equal to dense causal attention (tested against
`dense_causal_attention` in tests/test_fedllm.py) and differentiable — the
transpose of ppermute/all_to_all is the reverse rotation, so the backward
pass rides the same ring.

All functions take [B, T, H, D] Q/K/V with T already RoPE'd/global-position
encoded by the caller (the model passes pos_offset = axis_index * T_local).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -1e9  # finite "-inf": keeps exp() NaN-free for fully-masked rows


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_offset=0, k_offset=0,
                           window: int | None = None,
                           block: int = 0) -> jax.Array:
    """Reference causal attention. q: [B, T, H, D], k/v: [B, T, KV, D] with
    KV dividing H (query head i reads KV head i // (H / KV)) ->
    [B, T, H, D]. Offsets give the global position of element 0 (used when
    chunks of a sharded sequence are compared). `window`: position i sees
    j only where 0 <= i - j < window. `block` > 0 is the block-causal mask
    of a block-diffusion model: i sees j iff j // block <= i // block
    (causal over blocks, both ways inside one, blocks counted from 0)."""
    scale = q.shape[-1] ** -0.5
    qpos = q_offset + jnp.arange(q.shape[1])
    kpos = k_offset + jnp.arange(k.shape[1])
    if block:
        mask = qpos[:, None] // block >= kpos[None, :] // block
    else:
        mask = qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)
    if k.shape[2] != q.shape[2]:
        b, t, h, d = q.shape
        qg = q.reshape(b, t, k.shape[2], h // k.shape[2], d)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * scale
        p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(q.shape)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _merge_block(carry, q, k, v, qpos0, kpos0, scale):
    """One online-softmax accumulation step (the flash-attention recurrence:
    running max m, normalizer l, unnormalized output o)."""
    o, m, l = carry
    tq, tk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale          # [B,H,Tq,Tk]
    qpos = qpos0 + jnp.arange(tq)
    kpos = kpos0 + jnp.arange(tk)
    mask = qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(-1))                        # [B,H,Tq]
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    # fully-masked entries contribute exp(_NEG - m_new) ~ 0 once any real
    # block has been seen; before that they add mass that the next corr
    # factor exp(_NEG - m_real) zeroes out.
    l = l * corr + p.sum(-1)
    o = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return o, m_new, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str) -> jax.Array:
    """Causal ring attention inside a shard_map body.

    q/k/v: [B, T_local, H, D] — the local chunk of a sequence sharded
    contiguously over `axis_name` (device i holds tokens
    [i*T_local, (i+1)*T_local)). Returns the local output chunk [B, T_local,
    H, D], numerically equal to dense causal attention over the full
    sequence.

    K/V rotate: at step s, this device holds the chunk originally on device
    (my - s) mod n; n steps visit every chunk once. The causal mask falls out
    of comparing global positions, so fully-future blocks contribute nothing
    (their work is wasted MXU cycles — acceptable; a skew-schedule variant
    can skip them later)."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    # derive the accumulator from q so it inherits q's full varying-axes set
    # (ring may be nested inside other mesh axes, e.g. a `silos` scan; a
    # fresh zeros array would be typed replicated and break the loop carry)
    z = jnp.einsum("bqhd->bhqd", qf) * 0.0
    acc = (
        z,                                           # o (unnormalized)
        z.sum(-1) + _NEG,                            # m
        z.sum(-1),                                   # l
    )
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, state):
        acc, kk, vv = state
        src = jnp.mod(my - i, n)
        acc = _merge_block(acc, qf, kk.astype(jnp.float32),
                           vv.astype(jnp.float32),
                           my * t, src * t, scale)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return acc, kk, vv

    (o, _m, l), _, _ = jax.lax.fori_loop(0, n, body, (acc, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str) -> jax.Array:
    """Ulysses-style sequence parallelism inside a shard_map body: all_to_all
    converts the seq-sharded layout [B, T/n, H, D] into a head-sharded layout
    [B, T, H/n, D], dense causal attention runs on full sequences per head
    group, and the output all_to_alls back to seq-sharded. Requires
    H % axis_size == 0."""
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"{axis_name!r} axis size ({n})")
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name,
        split_axis=2, concat_axis=1, tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)          # [B, T, H/n, D]
    o = dense_causal_attention(qh, kh, vh)
    return jax.lax.all_to_all(
        o, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True)
