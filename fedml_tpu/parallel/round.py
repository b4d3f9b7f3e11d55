"""The federated round as one XLA program over a device mesh.

This is the TPU-native core (BASELINE.json north star: `backend=XLA`). The
reference runs a round as processes exchanging messages — broadcast params,
per-process local training, reduce(SUM) of weight-premultiplied params
(reference: simulation/nccl/base_framework/common.py:180-226,
LocalAggregator.py:69-92). Here the whole round is a single jitted function:

    gather(sampled shards) -> shard_map over `clients` mesh axis:
        scan over this chip's clients (optionally chunked-vmap within the scan)
        each client: lax.scan local SGD -> update
        weight-premultiplied partial sums            (== LocalAggregator:79-81)
    -> psum over `clients`                           (== dist.reduce(SUM))
    -> server_update, replicated                     (== rank-0 aggregate)

Broadcast is implicit (replicated sharding); there is no server process at all.
More sampled clients than chips -> the per-chip scan sequentially simulates its
assigned clients, exactly the fedavg_seq/NCCL-sim worker-sequential pattern
(reference: simulation/mpi/fedavg_seq/, nccl/README.md:3-25).

FULL-mode aggregators (robust defenses that need every client update
materialized — Krum, median, ...) use all_gather instead of psum.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..core.algorithm import FULL, ClientMetrics, FedAlgorithm, ServerState
from ..ops import tree as tu
from ..utils.metrics import track_jit

Pytree = Any


def _localize(tree: Pytree, axis: str) -> Pytree:
    """Convert replicated values to device-varying inside a shard_map body,
    so gradients w.r.t. them stay per-device instead of auto-psum'd."""
    return jax.tree.map(
        lambda x: (jax.lax.pcast(x, (axis,), to="varying")
                   if hasattr(x, "dtype") else x), tree)


class RoundOutput(NamedTuple):
    server_state: ServerState
    client_states: Pytree          # full stacked [num_clients_total, ...] or None
    metrics: dict                  # {"train_loss": ..., "train_acc": ..., "n": ...}
    hook_state: Pytree = None      # defense/plugin state threaded across rounds


def _tree_vdot(a: Pytree, b: Pytree) -> jax.Array:
    """f32 dot product over matching pytrees (bf16 updates upcast so norms
    don't saturate)."""
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32))
               for x, y in zip(leaves_a, leaves_b))


def _client_health(upds: Pytree, agg: Pytree, loss_per_client: jax.Array,
                   summed_metrics) -> dict:
    """Per-client run-health stats (ISSUE 3 tentpole), computed IN-JIT so
    they ride the round's existing metrics transfer — zero extra host syncs:

      update_norm  — L2 norm of each client's update,
      cosine       — cosine similarity of each update to the aggregate
                     (the pre-postprocess aggregate: the raw consensus,
                     before DP noise / defense post-processing perturb it),
      loss_delta   — each client's mean training loss minus the cohort's
                     weighted mean loss this round.

    `upds` is the stacked [m, ...] update pytree, `agg` the aggregated
    update, `loss_per_client` the [m] per-client mean loss (0 for zero-
    weight mesh-padding duplicates — run_clients already zeroed their
    metrics), `summed_metrics` the cohort-summed ClientMetrics.
    """
    norms = jax.vmap(lambda u: jnp.sqrt(jnp.maximum(_tree_vdot(u, u), 0.0)))(
        upds)
    dots = jax.vmap(lambda u: _tree_vdot(u, agg))(upds)
    agg_norm = jnp.sqrt(jnp.maximum(_tree_vdot(agg, agg), 0.0))
    cosine = dots / jnp.maximum(norms * agg_norm, 1e-12)
    cohort = (summed_metrics.loss_sum.astype(jnp.float32)
              / jnp.maximum(summed_metrics.count, 1.0))
    return {"update_norm": norms, "cosine": cosine,
            "loss_delta": loss_per_client - cohort}


def _per_client_loss(mets) -> jax.Array:
    """[m] mean training loss per client from stacked ClientMetrics."""
    return (mets.loss_sum.astype(jnp.float32)
            / jnp.maximum(mets.count, 1.0))


class RoundParts(NamedTuple):
    """The round engine decomposed into its chunk-streamable pieces
    (ISSUE 8 tentpole). `round_body` is zero_carry + one chunk_body call +
    finalize_body fused into one traceable function — so the chunked driver
    (simulation/simulator.py cohort_chunk) executes EXACTLY the arithmetic
    the single-shot program executes, just split across jit calls with the
    partial-aggregate carry crossing the host. That structural identity is
    what makes chunked == unchunked bit-identical: the per-device weighted
    sums accumulate group-by-group in the same order either way, and the
    one cross-device reduction happens once, at finalize, in both."""
    zero_carry: Callable      # (server_state, full_cstates, ids, shards) -> carry
    chunk_body: Callable      # (carry, server_state, shards, ids, w, rng, off) -> carry
    finalize_body: Callable   # (server_state, carry, ids, w, rng, hook_state) -> RoundOutput
    round_body: Callable      # the fused single-shot body (build_round_fn)
    make_carry: Callable      # host-side zero-carry allocator (chunked driver)


def make_round_parts(
    alg: FedAlgorithm,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
    group_size: int = 1,
    aggregate_full: Optional[Callable[[Pytree, jax.Array, dict], tuple]] = None,
    postprocess_update: Optional[Callable[[Pytree, jax.Array], Pytree]] = None,
    postprocess_agg: Optional[Callable[[Pytree, dict], Pytree]] = None,
    num_real_clients: Optional[int] = None,
    health_stats: bool = False,
    client_dropout: float = 0.0,
    client_straggler: float = 0.0,
) -> RoundParts:
    """Build the traceable round pieces shared by `build_round_fn` (one round
    per jit call), `build_block_fn` (K rounds scanned inside one jit), and
    `build_chunk_fns` (an m-client cohort streamed through HBM-bounded
    chunks, ISSUE 8).

    round_fn(server_state, full_client_states, data, ids, weights, rng,
             hook_state) -> RoundOutput
    where data = {"x": [N, S, ...], "y": [N, S], "mask": [N, S]} (device-resident,
    client-sharded when a mesh is given), ids = [m] sampled client indices
    (host-driven sampling for reference parity — fedavg_api.py:127 seeds np by
    round), weights = [m] aggregation weights.

    group_size: clients vmapped together inside the per-chip scan (G-way
    batching of client simulation; G=1 is the pure-sequential NCCL-sim shape).
    postprocess_update: per-client update transform applied before aggregation
    (compression, local DP, attacks — the on_after_local_training hook site,
    reference: core/alg_frame/client_trainer.py:56-59).
    aggregate_full: FULL-mode aggregation fn(stacked_updates, weights, ctx)
    -> (agg, new_hook_state) — robust defenses/attacks that need every client
    update materialized (forces the all_gather path). ctx =
    {"rng", "ids", "state", "params"} (the on_before/on_aggregation hook
    sites, reference: core/alg_frame/server_aggregator.py:42-76).
    postprocess_agg: fn(agg, ctx) -> agg applied to the aggregate before the
    server update (central DP noise, SLSGD/CRFL post-processing — the
    on_after_aggregation site, server_aggregator.py:79-83).
    num_real_clients: the number of genuinely sampled clients. When the
    simulator pads ids to a mesh multiple with zero-weight duplicates
    (simulator._pad_ids), FULL-mode hooks must not see the duplicate rows —
    unweighted statistics (krum distances, medians, foolsgold history) would
    be silently biased by them; the engine slices U/weights/ids back to the
    real prefix before invoking the hook.
    health_stats: when True the round's metrics dict carries a "health"
    sub-dict of per-client [m] f32 arrays (update_norm / cosine /
    loss_delta — see `_client_health`) computed inside the program, riding
    the same device→host transfer as the scalar metrics. Mesh-padding
    duplicate rows are included (the host masks them by weight). Health
    stats are observation-only: they change no training output.
    client_dropout / client_straggler: chaos-plane client-fault rates
    (ISSUE 4, `common_args.extra.chaos`). Seeded per-round masks are drawn
    IN-JIT from the round rng (so blocked and per-round execution draw
    bit-identical masks) and keyed by client id (so a mesh-padding
    duplicate shares its source's fate). A faulted client still computes —
    shapes stay static — but its aggregation weight is zeroed, so every
    weight-driven aggregate (the weighted-mean paths and the default FULL
    hook) reweights over the survivors without a host round-trip, its
    training metrics are excluded, and its persistent client state keeps
    the pre-round value (a lost report never happened). Weight-IGNORING
    full-set aggregators get the survivor mask as ctx["fault_keep"] and
    must honor it themselves (static shapes cannot shrink the cohort).
    A round where EVERY sampled client faults degrades to a zero aggregate
    — a no-op server step for delta-style algorithms — rather than a NaN.
    The drawn masks ride the metrics dict as `metrics["faults"]`
    ({"dropped", "straggled"}: [m] f32 0/1) so the host health plane can
    account participation and flag the injected faults.
    """
    use_full = aggregate_full is not None or alg.agg_mode == FULL
    if use_full and aggregate_full is None:
        # algorithm declared FULL aggregation but no hook was supplied:
        # default to the weighted mean over the materialized update set
        def aggregate_full(stacked, w, ctx):
            return tu.tree_weighted_mean(stacked, w), ctx["state"]

    # what must be materialized per client: FULL hooks need every update
    # stacked; the health plane needs stacked updates + per-client metrics.
    # Pure LINEAR aggregation needs NEITHER — the weighted sums accumulate
    # in the scan carry, so HBM holds O(group) updates instead of O(cohort).
    collect_upds = use_full or health_stats
    collect_cmets = bool(health_stats)
    has_cstate = alg.client_state_init is not None
    chaos_on = client_dropout > 0.0 or client_straggler > 0.0
    dv = int(mesh.devices.size) if mesh is not None else 1

    def one_client(bcast, shard, cstate, rng, weight):
        with jax.named_scope("fed.local_sgd"):
            upd, new_state, met = alg.client_update(bcast, shard, cstate, rng)
            if postprocess_update is not None:
                upd = postprocess_update(upd, rng)
        return upd, new_state, met

    def client_structs(server_state, full_cstates, shards):
        """(upd, nstate, met) ShapeDtypeStructs of ONE client — the leaf
        shapes the accumulator carry is built from. Abstract eval only, so
        it works on tracers (fused body), concrete arrays, and
        ShapeDtypeStructs (host-side make_carry) alike."""
        bc = jax.eval_shape(alg.broadcast, server_state)
        sh1 = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), shards)
        cs1 = (jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), full_cstates)
            if has_cstate else jax.ShapeDtypeStruct((), jnp.float32))
        key = jax.eval_shape(lambda: jax.random.key(0))
        w = jax.ShapeDtypeStruct((), jnp.float32)
        return jax.eval_shape(one_client, bc, sh1, cs1, key, w)

    def zero_carry(server_state, full_cstates, ids, shards):
        """The partial-aggregate carry at the start of a round: per-device
        weighted-sum accumulators (leading axis = mesh size, so the one
        cross-device reduction can happen once, at finalize), the stacked
        [m] collection buffers the FULL/health paths fill chunk by chunk
        via dynamic_update_slice, and the client-state plane: the cohort's
        states are gathered HERE, at round start — every chunk computes
        from pre-round state (exactly as the single-shot gather does), new
        states buffer into `ns` per chunk, and ONE scatter at finalize
        commits them. Scattering per chunk instead would corrupt state
        when a mesh-pad duplicate lands in a later chunk than its source:
        the duplicate would recompute from its source's ALREADY-UPDATED
        state and overwrite the real update with a second step."""
        m = ids.shape[0]
        upd_s, ns_s, met_s = client_structs(server_state, full_cstates,
                                            shards)
        carry = {
            # FULL mode aggregates from the stacked buffer, so the weighted
            # sum accumulators would be dead weight (params x mesh) threaded
            # through every donated chunk call — empty subtrees instead
            "num": (jax.tree.map(
                lambda s: jnp.zeros((dv,) + s.shape, s.dtype), upd_s)
                if not use_full else {}),
            "den": (jnp.zeros((dv,), jnp.float32) if not use_full else {}),
            "msum": jax.tree.map(
                lambda s: jnp.zeros((dv,) + s.shape, s.dtype), met_s),
            "cstates": full_cstates,
            "bufs": {},
        }
        if has_cstate:
            carry["bufs"]["cs"] = jax.tree.map(
                lambda a: jnp.take(a, ids, axis=0), full_cstates)
            carry["bufs"]["ns"] = jax.tree.map(
                lambda s: jnp.zeros((m,) + s.shape, s.dtype), ns_s)
        if collect_upds:
            carry["bufs"]["u"] = jax.tree.map(
                lambda s: jnp.zeros((m,) + s.shape, s.dtype), upd_s)
        if collect_cmets:
            carry["bufs"]["m"] = jax.tree.map(
                lambda s: jnp.zeros((m,) + s.shape, s.dtype), met_s)
        return carry

    def make_carry(server_state, full_cstates, ids, chunk_struct):
        """Host-side zero-carry allocator for the chunked driver (once per
        round). `ids` is the full padded [m] cohort row; chunk_struct: the
        ShapeDtypeStruct tree of ONE chunk's {"x","y","mask"} (client axis
        leading). Accumulators and collection buffers are placed client-/
        device-sharded so every chunk program updates them in place
        (donated)."""
        carry = zero_carry(server_state, full_cstates, jnp.asarray(ids),
                           chunk_struct)
        if mesh is not None:
            sh = NamedSharding(mesh, P(axis))
            rep = NamedSharding(mesh, P())
            # commit EVERY leaf (accumulators/buffers client-sharded, the
            # full client-state tree replicated): the jit cache keys on
            # input shardings, so an uncommitted first-round carry would
            # buy one extra compile per program before the layouts the
            # chunk outputs carry become the steady state
            carry = {
                k: jax.tree.map(
                    lambda a: jax.device_put(
                        a, sh if k in ("num", "den", "msum", "bufs") else rep),
                    v)
                for k, v in carry.items()
            }
        return carry

    def run_clients_acc(bcast, shards, cstates, rngs, weights, acc, bufs, off):
        """Scan over local clients (leading axis) in G-way vmapped groups,
        accumulating the weighted update sum / weight sum / metric sums into
        `acc` ([1, ...]-leading local accumulator slices) and writing any
        collected stacks into `bufs` at local row `off`. Returns
        (acc, stacked new states, bufs)."""
        m_local = shards["y"].shape[0]
        g = max(1, min(group_size, m_local))
        while m_local % g:  # largest divisor of m_local not exceeding group_size
            g -= 1
        n_groups = m_local // g

        def body(car, inp):
            sh, cs, rg, w = inp
            upd, ns, met = jax.vmap(one_client, in_axes=(None, 0, 0, 0, 0))(
                bcast, sh, cs, rg, w
            )
            # zero-weight clients are mesh-padding duplicates (simulator
            # _pad_ids); keep them out of the reported training metrics
            num, den, ms = car
            with jax.named_scope("fed.accumulate"):
                met = jax.tree.map(
                    lambda a: a * (w > 0).astype(a.dtype), met)
                if not use_full:
                    # weight-premultiplied group sum folded into the carry
                    # — the NCCL-sim reduce (common.py:197-207) restructured
                    # as a sequential accumulation so a chunk boundary
                    # (ISSUE 8) cannot change the addition order
                    num = jax.tree.map(
                        lambda n, u: n + jnp.sum(
                            u * w.reshape((-1,) + (1,) * (u.ndim - 1)).astype(
                                u.dtype),
                            axis=0)[None],
                        num, upd)
                    den = den + jnp.sum(w)[None]
                ms = jax.tree.map(
                    lambda a, b: a + jnp.sum(b, axis=0)[None], ms, met)
            ys = {"ns": ns}
            if collect_upds:
                ys["u"] = upd
            if collect_cmets:
                ys["m"] = met
            return (num, den, ms), ys

        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, g) + a.shape[1:]),
            (shards, cstates, rngs, weights),
        )
        # a scope is an operation's INNERMOST name (PERF.md section 3): the
        # body's parts keep their own, and what stays `fed.collect` is the
        # loop's own work, slicing each group's inputs and stacking its
        # per-client updates, states and metrics as scan outputs, and below
        # their way into the round's buffers
        with jax.named_scope("fed.collect"):
            acc, ys = jax.lax.scan(body, acc, grouped)
        ungroup = lambda t: jax.tree.map(
            lambda a: a.reshape((m_local,) + a.shape[2:]), t)
        with jax.named_scope("fed.collect"):
            nstates = ungroup(ys["ns"])
            if collect_upds:
                bufs = {**bufs, "u": jax.tree.map(
                    lambda b, u: jax.lax.dynamic_update_slice_in_dim(
                        b, u, off, 0),
                    bufs["u"], ungroup(ys["u"]))}
            if collect_cmets:
                bufs = {**bufs, "m": jax.tree.map(
                    lambda b, u: jax.lax.dynamic_update_slice_in_dim(
                        b, u, off, 0),
                    bufs["m"], ungroup(ys["m"]))}
        return acc, nstates, bufs

    def fault_masks(rng, ids):
        """Seeded per-client fault draws, keyed by client id — a chunk's
        draws are bit-identical to the same ids' draws in the single-shot
        program, and a mesh-padding duplicate shares its source's fate."""
        frng = jax.random.fold_in(rng, 0xFA17)

        def fault_mask(rate, salt):
            if rate <= 0.0:
                return jnp.zeros(ids.shape, bool)
            r = jax.random.fold_in(frng, salt)
            return jax.vmap(lambda i: jax.random.bernoulli(
                jax.random.fold_in(r, i), rate))(ids)

        dropped = fault_mask(client_dropout, 1)
        # a crashed client can't also straggle; keep the masks disjoint
        straggled = jnp.logical_and(fault_mask(client_straggler, 2),
                                    jnp.logical_not(dropped))
        keep = jnp.logical_not(jnp.logical_or(dropped, straggled))
        return dropped, straggled, keep

    def chunk_body(carry, server_state, shards, ids, weights, rng, off):
        """Accumulate one cohort chunk into the carry. `off` is the
        PER-DEVICE row offset of this chunk inside the round's stacked
        buffers (traced, so one compiled chunk program serves every chunk
        index). The chunk's clients are laid out per-device: rows
        [k*c, (k+1)*c) belong to device k — the same client→device
        assignment the single-shot program gives them, which is what keeps
        per-device accumulation order (and therefore results) bit-identical
        to the unchunked path. Client states are READ from the round-start
        gather (carry bufs "cs") and new states buffered into "ns" — never
        scattered mid-round, so a pad duplicate in a later chunk cannot
        observe (and corrupt) its source's already-updated state."""
        with jax.named_scope("fed.broadcast"):
            bcast = alg.broadcast(server_state)
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(ids)
        keep = jnp.ones(ids.shape, bool)
        if chaos_on:
            # zeroed weight = lost report on every WEIGHT-DRIVEN aggregate:
            # the carry accumulates only survivor-weighted sums, so the
            # aggregate renormalizes over survivors at finalize with no
            # host round-trip and no shape change (see finalize_body for
            # the weight-IGNORING full-set aggregator contract)
            _, _, keep = fault_masks(rng, ids)
            weights = weights * keep.astype(weights.dtype)
        acc = (carry["num"], carry["den"], carry["msum"])
        bufs = carry["bufs"]

        def run_chunk(bc, sh, rg, w, kp, a, bf, o):
            """Per-device chunk work: slice this chunk's pre-round client
            states, scan the clients, fault-restore, and write the new
            states into the round buffer at `o`."""
            c_local = sh["y"].shape[0]
            cs = (jax.tree.map(
                lambda b: jax.lax.dynamic_slice_in_dim(b, o, c_local, 0),
                bf["cs"]) if has_cstate else jnp.zeros((c_local,)))
            a, ns, bf = run_clients_acc(bc, sh, cs, rg, w, a, bf, o)
            if has_cstate:
                if chaos_on:
                    # a faulted client's report was lost: its persistent
                    # state (SCAFFOLD c_i, FedDyn h_i, ...) must keep the
                    # pre-round value, exactly as if never dispatched
                    ns = jax.tree.map(
                        lambda new, old: jnp.where(
                            kp.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old),
                        ns, cs)
                bf = {**bf, "ns": jax.tree.map(
                    lambda b, n: jax.lax.dynamic_update_slice_in_dim(
                        b, n, o, 0),
                    bf["ns"], ns)}
            return a, bf

        if mesh is None:
            acc, bufs = run_chunk(bcast, shards, rngs, weights, keep,
                                  acc, bufs, off)
        else:
            spec_c, spec_r = P(axis), P()

            @functools.partial(
                shard_map,
                mesh=mesh,
                in_specs=(spec_r, spec_c, spec_c, spec_c, spec_c, spec_c,
                          spec_c, spec_r),
                out_specs=(spec_c, spec_c),
            )
            def block(bc, sh, rg, w, kp, a, bf, o):
                # Mark the replicated broadcast as device-varying before any
                # differentiation: shard_map treats grads w.r.t. replicated
                # values as global (auto-psum across the mesh), but local SGD
                # needs per-client gradients. pcast localizes the copy.
                bc = _localize(bc, axis)
                o = _localize(o, axis)
                return run_chunk(bc, sh, rg, w, kp, a, bf, o)

            acc, bufs = block(bcast, shards, rngs, weights, keep,
                              acc, bufs, off)
        out = dict(carry)
        out["num"], out["den"], out["msum"] = acc
        out["bufs"] = bufs
        return out

    def finalize_body(server_state, carry, ids, weights, rng, hook_state):
        """Close the round: ONE cross-device reduction of the accumulated
        per-device partials, the FULL-mode hook over the collected stack,
        post-processing, the server step, and the metrics row."""
        with jax.named_scope("fed.finalize"):
            return _finalize(server_state, carry, ids, weights, rng,
                             hook_state)

    def _finalize(server_state, carry, ids, weights, rng, hook_state):
        agg_rng = jax.random.fold_in(rng, 0x5EC)
        faults = None
        keep = None
        if chaos_on:
            # recomputed over the full [m] row — draws are keyed by client
            # id, so these are bit-for-bit the masks the chunks drew (and
            # in the fused body XLA CSEs the two computations away)
            dropped, straggled, keep = fault_masks(rng, ids)
            weights = weights * keep.astype(weights.dtype)
            faults = {"dropped": dropped.astype(jnp.float32),
                      "straggled": straggled.astype(jnp.float32)}
        ctx = {"rng": agg_rng, "ids": ids, "state": hook_state,
               "params": server_state.params}
        if keep is not None:
            # FULL-mode hooks that ignore weights (median/krum families)
            # need the survivor mask explicitly: static shapes cannot
            # shrink the cohort, so weight-IGNORING aggregators must honor
            # ctx["fault_keep"] themselves
            ctx["fault_keep"] = keep
        if use_full:
            upds = carry["bufs"]["u"]
            mr = num_real_clients
            if mr is not None and mr < ids.shape[0]:
                # mesh-padding duplicates must not bias unweighted
                # statistics (krum distances, medians): slice the real
                # prefix before invoking the hook
                u = jax.tree.map(lambda a: a[:mr], upds)
                w_ = weights[:mr]
                cx = {**ctx, "ids": ids[:mr]}
                if keep is not None:
                    cx["fault_keep"] = keep[:mr]
            else:
                u, w_, cx = upds, weights, ctx
            agg, hook_state = aggregate_full(u, w_, cx)
        else:
            num = jax.tree.map(lambda a: jnp.sum(a, axis=0), carry["num"])
            den = jnp.sum(carry["den"])
            agg = jax.tree.map(
                lambda a: a / jnp.maximum(den, 1e-12).astype(a.dtype), num)
        summed = jax.tree.map(lambda a: jnp.sum(a, axis=0), carry["msum"])
        health = None
        if health_stats:
            with jax.named_scope("fed.health"):
                health = _client_health(
                    carry["bufs"]["u"], agg,
                    _per_client_loss(carry["bufs"]["m"]), summed)
        if postprocess_agg is not None:
            agg = postprocess_agg(agg, ctx)
        new_server = alg.server_update(server_state, agg)
        n = jnp.maximum(summed.count, 1.0)
        metrics = {
            "train_loss": summed.loss_sum / n,
            "train_acc": summed.correct / n,
            "n_samples": summed.count,
        }
        if summed.extra:        # what the model counted of itself
            metrics.update(summed.extra)
        if health:
            metrics["health"] = health
        if faults:
            metrics["faults"] = faults
        full_cstates = carry["cstates"]
        if has_cstate:
            # the ONE client-state scatter of the round: every buffered row
            # was computed from pre-round state, so pad duplicates write
            # values bit-identical to their source rows (order-independent)
            full_cstates = jax.tree.map(
                lambda full, new: full.at[ids].set(new),
                carry["cstates"], carry["bufs"]["ns"])
        return RoundOutput(new_server, full_cstates, metrics, hook_state)

    def round_body(server_state, full_cstates, data, ids, weights, rng,
                   hook_state):
        shards = {
            "x": jnp.take(data["x"], ids, axis=0),
            "y": jnp.take(data["y"], ids, axis=0),
            "mask": jnp.take(data["mask"], ids, axis=0),
        }
        carry = zero_carry(server_state, full_cstates, ids, shards)
        carry = chunk_body(carry, server_state, shards, ids, weights, rng,
                           jnp.zeros((), jnp.int32))
        return finalize_body(server_state, carry, ids, weights, rng,
                             hook_state)

    return RoundParts(zero_carry, chunk_body, finalize_body, round_body,
                      make_carry)


def build_round_fn(
    alg: FedAlgorithm,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
    group_size: int = 1,
    aggregate_full: Optional[Callable[[Pytree, jax.Array, dict], tuple]] = None,
    postprocess_update: Optional[Callable[[Pytree, jax.Array], Pytree]] = None,
    postprocess_agg: Optional[Callable[[Pytree, dict], Pytree]] = None,
    num_real_clients: Optional[int] = None,
    health_stats: bool = False,
    client_dropout: float = 0.0,
    client_straggler: float = 0.0,
) -> Callable:
    """Build the jitted single-round function (see `make_round_parts` for the
    argument contract)."""
    round_body = make_round_parts(
        alg, mesh, axis, group_size, aggregate_full, postprocess_update,
        postprocess_agg, num_real_clients, health_stats,
        client_dropout, client_straggler,
    ).round_body
    # donate server/client/hook state: all three are dead after the call, and
    # the hook state can be a [N, D] defense history that must update in place.
    # track_jit keeps PR 1's retrace guard on as a metric: gauge
    # xla.compiles.round_fn / counter xla.retraces.round_fn — and, on each
    # compile, captures the program's cost/memory analysis into the XLA
    # ledger (xla.program.*.round_fn — utils/xla_ledger.py, ISSUE 17).
    return track_jit(jax.jit(round_body, donate_argnums=(0, 1, 6)),
                     "round_fn")


def build_block_fn(
    alg: FedAlgorithm,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
    group_size: int = 1,
    aggregate_full: Optional[Callable[[Pytree, jax.Array, dict], tuple]] = None,
    postprocess_update: Optional[Callable[[Pytree, jax.Array], Pytree]] = None,
    postprocess_agg: Optional[Callable[[Pytree, dict], Pytree]] = None,
    num_real_clients: Optional[int] = None,
    health_stats: bool = False,
    client_dropout: float = 0.0,
    client_straggler: float = 0.0,
) -> Callable:
    """Build the jitted ROUND-BLOCK function: K federated rounds as one XLA
    program, `lax.scan` over the exact same round body `build_round_fn` jits.

    block_fn(server_state, full_client_states, data, ids, weights, base_rng,
             rounds, hook_state) -> RoundOutput
    where ids/weights are the host-precomputed schedules stacked to [K, m]
    (round-seeded sampling + `_pad_ids` padding + LPT balancing run on the
    host exactly as in per-round mode), rounds is the [K] int32 vector of
    global round indices, and base_rng is the run's root PRNG key. The body
    derives each round's key as `fold_in(base_rng, round_idx)` — bit-for-bit
    the key the per-round driver passes — so a K-block scan replays K
    individual rounds exactly, while paying ONE dispatch and returning
    stacked [K] metrics for ONE host transfer per block.

    K is baked into the program via the leading axis of `ids`; callers must
    keep the block shape fixed across calls (the simulator runs ragged tail
    blocks through the per-round path) or pay a retrace per distinct K.
    """
    round_body = make_round_parts(
        alg, mesh, axis, group_size, aggregate_full, postprocess_update,
        postprocess_agg, num_real_clients, health_stats,
        client_dropout, client_straggler,
    ).round_body

    def block_body(server_state, full_cstates, data, ids, weights, base_rng,
                   rounds, hook_state):
        def step(carry, xs):
            st, cs, hs = carry
            ids_r, w_r, r = xs
            out = round_body(st, cs, data, ids_r, w_r,
                             jax.random.fold_in(base_rng, r), hs)
            return (out.server_state, out.client_states, out.hook_state), \
                out.metrics
        (st, cs, hs), metrics = jax.lax.scan(
            step, (server_state, full_cstates, hook_state),
            (ids, weights, rounds))
        return RoundOutput(st, cs, metrics, hs)

    # same donation contract as the single-round program; the scan carry
    # aliases the donated buffers so K rounds update state in place
    return track_jit(jax.jit(block_body, donate_argnums=(0, 1, 7)),
                     "block_fn")


def build_chunk_fns(
    alg: FedAlgorithm,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
    group_size: int = 1,
    aggregate_full: Optional[Callable[[Pytree, jax.Array, dict], tuple]] = None,
    postprocess_update: Optional[Callable[[Pytree, jax.Array], Pytree]] = None,
    postprocess_agg: Optional[Callable[[Pytree, dict], Pytree]] = None,
    num_real_clients: Optional[int] = None,
    health_stats: bool = False,
    client_dropout: float = 0.0,
    client_straggler: float = 0.0,
) -> tuple[Callable, Callable, Callable]:
    """Chunked-cohort execution (ISSUE 8 tentpole): the round split into
    HBM-bounded jit calls so a cohort is bounded by HOST RAM, not device
    memory. Returns (chunk_fn, finalize_fn, make_carry):

      make_carry(server_state, full_cstates, m, chunk_struct) -> carry
      chunk_fn(carry, server_state, chunk_data, chunk_ids, chunk_weights,
               rng, offset) -> carry                         [donates carry]
      finalize_fn(server_state, carry, ids, weights, rng, hook_state)
               -> RoundOutput          [donates server_state, carry, hook]

    The driver (simulation/simulator.py) host-gathers each chunk's client
    data and streams it in (double-buffered — simulation/ingest.py); the
    partial aggregate rides the donated carry across chunk calls; finalize
    performs the ONE cross-device reduction, the server step, and the
    metrics row. Because `round_body` is literally make_carry + one
    chunk_body + finalize_body fused, the chunked path is bit-identical to
    the single-shot program (pinned in tests/test_sim_scale.py) whenever
    the padded cohort, the LPT schedule row, and the client-group size
    line up — which they do for any cohort divisible by the chunk size.

    Caveats: in-jit health stats cannot ride chunked rounds (the cosine-
    to-aggregate stat needs every update against the FINAL aggregate; the
    chunked engine's whole point is not materializing the cohort), so
    health_stats is rejected here. FULL-mode aggregation still works —
    the updates ARE materialized into the carry's stacked buffer, so only
    the DATA transfer is chunk-bounded, not update memory (that is
    inherent to full-set aggregators).
    """
    if health_stats:
        raise ValueError(
            "health_stats cannot ride chunked rounds: cosine-to-aggregate "
            "needs the full update stack; run unchunked or disable "
            "train_args.extra.health_stats")
    parts = make_round_parts(
        alg, mesh, axis, group_size, aggregate_full, postprocess_update,
        postprocess_agg, num_real_clients, health_stats,
        client_dropout, client_straggler,
    )
    chunk_fn = track_jit(jax.jit(parts.chunk_body, donate_argnums=(0,)),
                         "chunk_fn")
    finalize_fn = track_jit(
        jax.jit(parts.finalize_body, donate_argnums=(0, 1, 5)),
        "finalize_fn")
    return chunk_fn, finalize_fn, parts.make_carry


def shard_fed_data(data: dict, mesh: Optional[Mesh], axis: str = "clients") -> dict:
    """device_put the stacked client arrays, sharded over the client axis.

    The layout comes from the ONE partition-rule registry
    (parallel/partition.py `fed_data_rules`): {"x","y","mask"} shard their
    leading client axis over `axis`. An unexpected data key is a hard
    error at placement time — not a silently replicated array that
    multiplies host->device transfer by the mesh size."""
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in data.items()}
    from .partition import fed_data_rules, match_partition_rules

    specs = match_partition_rules(fed_data_rules(axis), data)
    return {k: jax.device_put(jnp.asarray(v), NamedSharding(mesh, specs[k]))
            for k, v in data.items()}


def resolve_param_specs(params: Pytree, rules="transformer_lm",
                        axis: str = "mp",
                        on_unmatched: str = "error") -> Pytree:
    """The TRAIN-side entry point to the partition-rule registry: the
    PartitionSpec tree server params are laid out with. Delegates to
    parallel/partition.resolve — the same call the serving DecodeEngine
    makes, so the train and serve spec tables for a model cannot drift
    (asserted identical in tests/test_partition.py). In production the
    CentralizedTrainer consumes this plane today; the federated round
    paths consume the registry through `shard_fed_data`, and composing an
    `mp` axis INTO the client-sharded shard_map programs (a 2-D
    clients x mp round) is the multichip rung this entry point exists
    for — see ROADMAP."""
    from .partition import resolve

    return resolve(rules, params, axis=axis, on_unmatched=on_unmatched)


def shard_server_params(params: Pytree, mesh: Mesh,
                        rules="transformer_lm", axis: str = "mp",
                        on_unmatched: str = "error") -> Pytree:
    """device_put server params with registry-resolved shardings before
    building a round program: the jitted round inherits the layout from
    its inputs (GSPMD propagates it through broadcast/update/aggregate).
    Works today on the NO-MESH round path (single-device clients loop, mp
    mesh for the model); the shard_map client paths declare their
    broadcast replicated, so wiring an mp axis into them is the pending
    multichip-rung change, not a config flip."""
    from .partition import shard_params

    return shard_params(params, mesh, rules, axis=axis,
                        on_unmatched=on_unmatched)
