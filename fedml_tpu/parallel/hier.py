"""Hierarchical federated round: 2-D (silos, intra) mesh in one XLA program.

The reference's hierarchical cross-silo mode gives each silo several GPUs and
runs torch DDP *inside* the silo while FedAvg runs *across* silos (reference:
python/fedml/__init__.py:342-390 spawns one process per intra-silo rank;
cross_silo/client/process_group_manager.py:8 builds the NCCL group;
fedml_trainer_dist_adapter.py:9 wraps the trainer in DDP).

TPU design: both levels are axes of ONE mesh —

    mesh = Mesh(devices.reshape(n_silos, intra), ("silos", "intra"))

- `silos` is the federated-parallel axis: sampled clients (silos) are sharded
  over it, aggregation is a weighted-mean psum over it (the DCN/outer level).
- `intra` is the data-parallel axis: each silo's local batch is sharded over
  it and the per-step gradient is psum'd over it (the NCCL-allreduce/inner
  level). XLA lays the inner psum on the fast ICI ring because `intra` is the
  minor mesh axis.

The inner SGD uses sum-CE gradients psum-normalized by the *global* masked
count, so the update equals the flat (unsharded) batch-mean gradient —
intra-silo DDP parity is exact (per batch), not approximate.

The message-driven composition of the same two levels (real DCN between
hosts) lives in cross_silo/hierarchical.py; this module is the
simulation/XLA shape (BASELINE.json config 4: hierarchical cross-silo).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.algorithm import FedAlgorithm, ServerState, make_batch_indices
from ..ops import tree as tu
from .round import _localize

Pytree = Any


def hier_local_sgd(
    apply_fn: Callable,
    params: Pytree,
    shard: dict,                # local slice {"x": [S_loc,...], "y", "mask"}
    batch_idx: jax.Array,       # [num_steps, B_loc] indices into the LOCAL slice
    opt: optax.GradientTransformation,
    data_axis: str,
):
    """Data-parallel local SGD inside a shard_map body: each `data_axis`
    device holds a sample shard; per step, sum-CE gradients are psum'd over
    the axis and normalized by the global masked count (== the DDP allreduce,
    reference: cross_silo/client/fedml_trainer_dist_adapter.py:9). Params stay
    replicated across `data_axis` because every device applies the identical
    psum'd update."""
    opt_state = opt.init(params)

    def step(carry, idx):
        p, s = carry
        batch = {k: v[idx] for k, v in shard.items()}

        def loss_sum(pp):
            logits = apply_fn({"params": pp}, batch["x"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"])
            lsum = (ce * batch["mask"]).sum()
            correct = ((jnp.argmax(logits, -1) == batch["y"])
                       * batch["mask"]).sum()
            return lsum, correct

        (lsum, correct), grads = jax.value_and_grad(loss_sum, has_aux=True)(p)
        cnt = jax.lax.psum(batch["mask"].sum(), data_axis)
        denom = jnp.maximum(cnt, 1.0)
        grads = jax.tree.map(
            lambda g: jax.lax.psum(g, data_axis) / denom.astype(g.dtype),
            grads)
        lsum = jax.lax.psum(lsum, data_axis)
        correct = jax.lax.psum(correct, data_axis)
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return (p, s), (lsum, correct, cnt)

    (params, _), (losses, corrects, counts) = jax.lax.scan(
        step, (params, opt_state), batch_idx)
    return params, (losses.sum(), corrects.sum(), counts.sum())


def make_hier_round(
    apply_fn: Callable,
    alg: FedAlgorithm,
    mesh: Mesh,
    opt: optax.GradientTransformation,
    batch_size: int,
    epochs: int,
    client_axis: str = "silos",
    data_axis: str = "intra",
) -> Callable:
    """Build the jitted hierarchical round.

    round_fn(server_state, data, ids, weights, rng) -> (server_state, metrics)
    with data = {"x": [N, S, ...], "y": [N, S], "mask": [N, S]} laid out
    P(silos, intra) (clients over silos, samples over intra — use
    `shard_hier_data`), ids = [m] sampled silo indices (m divisible by the
    silos axis size), weights = [m] aggregation weights.

    batch_size is the GLOBAL per-silo batch; each intra device takes
    batch_size // intra samples per step from its local sample shard
    (batch_size must be divisible by the intra axis size).

    The hierarchical path re-derives the client step itself (the inner loop
    needs per-step intra psums that alg.client_update cannot express), so it
    supports exactly the plain-delta algorithms: FedAvg / FedOpt. Everything
    else — per-step corrections (FedProx/SCAFFOLD), structured payloads
    (FedNova), robust FULL-mode aggregation — composes on the flat path
    (parallel/round.py). The reference's hierarchical mode is likewise
    FedAvg-only (python/fedml/__init__.py:342).
    """
    if alg.name not in ("FedAvg", "FedOpt"):
        raise ValueError(
            f"hierarchical rounds support plain-delta algorithms "
            f"(FedAvg/FedOpt), not {alg.name!r}; use parallel/round.py's flat "
            "client-parallel path for algorithms with per-step corrections "
            "or structured payloads")
    n_intra = mesh.shape[data_axis]
    if batch_size % n_intra:
        raise ValueError(
            f"batch_size={batch_size} must be divisible by the {data_axis!r} "
            f"axis size {n_intra} (each intra device takes an equal slice of "
            "every step's batch)")
    spec_r = P()
    spec_cd = P(client_axis, data_axis)   # [clients, samples, ...]
    spec_c = P(client_axis)

    def round_body(server_state: ServerState, data, ids, weights, rng):
        bcast = alg.broadcast(server_state)
        shards = {k: jnp.take(v, ids, axis=0) for k, v in data.items()}
        shards = jax.lax.with_sharding_constraint(
            shards, NamedSharding(mesh, spec_cd))
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(ids)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(spec_r, spec_cd, spec_c, spec_c),
            out_specs=(spec_r, spec_r),
        )
        def block(bc, sh, rg, w):
            bc = _localize(_localize(bc, client_axis), data_axis)
            s_loc = sh["y"].shape[1]
            b_loc = batch_size // n_intra

            def one_silo(carry, inp):
                sh_i, rg_i, w_i = inp
                idx = make_batch_indices(rg_i, s_loc, b_loc, epochs)
                p, (lsum, correct, cnt) = hier_local_sgd(
                    apply_fn, bc["params"], sh_i, idx, opt, data_axis)
                upd = tu.tree_sub(p, bc["params"])
                wi = w_i.astype(jnp.float32)
                # weight-premultiplied partial sums, as in the flat engine
                num = jax.tree.map(lambda a: a * wi.astype(a.dtype), upd)
                live = (w_i > 0).astype(jnp.float32)
                mets = (lsum * live, correct * live, cnt * live)
                return carry, (num, wi, mets)

            _, (nums, ws, mets) = jax.lax.scan(one_silo, None, (sh, rg, w))
            # outer level: weighted mean across all silos (the DCN aggregate,
            # reference: simulation/nccl/base_framework/common.py:197-207)
            num = jax.lax.psum(jax.tree.map(lambda a: a.sum(0), nums),
                               client_axis)
            den = jax.lax.psum(ws.sum(), client_axis)
            agg = jax.tree.map(
                lambda a: a / jnp.maximum(den, 1e-12).astype(a.dtype), num)
            # the aggregate is identical on every intra device (grads were
            # psum'd over intra each step) but still *typed* device-varying
            # over intra; pmean is a numerical identity that re-establishes
            # replication for the P() out_spec
            agg = jax.lax.pmean(agg, data_axis)
            summed = jax.lax.psum(
                jax.tree.map(lambda a: a.sum(0), mets), client_axis)
            return agg, summed

        agg, (lsum, correct, cnt) = block(bcast, shards, rngs, weights)
        new_server = alg.server_update(server_state, agg)
        n = jnp.maximum(cnt, 1.0)
        metrics = {"train_loss": lsum / n, "train_acc": correct / n,
                   "n_samples": cnt}
        return new_server, metrics

    return jax.jit(round_body, donate_argnums=(0,))


def shard_hier_data(data: dict, mesh: Mesh, client_axis: str = "silos",
                    data_axis: str = "intra") -> dict:
    """device_put stacked client data on the 2-D layout: clients over the
    silo axis, each client's samples over the intra axis."""
    sh = NamedSharding(mesh, P(client_axis, data_axis))
    return {k: jax.device_put(jnp.asarray(v), sh) for k, v in data.items()}
