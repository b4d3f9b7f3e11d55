"""FedLLM: federated LoRA fine-tuning of transformer LMs (BASELINE.md
workload 5; reference: python/spotlight_prj/fedllm/README.md:1 — the
reference fine-tunes LLaMA with HF peft + FedML cross-silo; this package is
the TPU-native equivalent).

Two compositions:

1. `federated_lora(...)` — the flat path: adapters ARE the federated model.
   `adapted_apply_fn` (`lora_apply_fn` for a TransformerLM: the same view,
   the adapted projections' backward in rank-r products) turns
   (adapters -> logits) into an ordinary apply fn, so the WHOLE existing
   stack — round engine (parallel/round.py), algorithms,
   compression, DP, defenses, cross-silo managers — trains and exchanges
   only adapter pytrees with zero new code. Base weights never move.

2. `make_fedllm_seq_round(...)` — the long-context path: one jitted round
   over a (silos, seq) mesh. Clients (silos) are sharded over `silos`;
   each client's token dimension is sharded over `seq` and attention runs
   as ring attention (parallel/seq.py) with K/V ppermute-rotating over ICI.
   Per-step adapter gradients are psum'd over `seq` (exact: sum-CE grads
   normalized by the global token count), aggregation is the usual
   weight-premultiplied psum over `silos`.

Sequence-parallel data layout: {"x": [N, S, T], "y": [N, S, T],
"mask": [N, S]} int32 token arrays, sharded P(silos, None, seq) — use
`shard_fedllm_data`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..algorithms.builtin import make_fedavg
from ..config import TrainArgs
from ..core.algorithm import FedAlgorithm, ServerState, make_batch_indices
from ..ops import tree as tu
from ..parallel.round import _localize
from ..parallel.seq import ring_attention, ulysses_attention
from .lora import count_params, lora_apply_fn, lora_init, lora_merge
from .moe import COUNTERS, fold_counters
from .transformer import TransformerLM, adapted_apply_fn

Pytree = Any

__all__ = [
    "TransformerLM", "lora_init", "lora_merge", "lora_apply_fn",
    "count_params", "federated_lora", "make_fedllm_seq_round",
    "shard_fedllm_data",
]


def federated_lora(model: TransformerLM, base_params: Pytree, t: TrainArgs,
                   rng: jax.Array, rank: int = 8, alpha: float = 16.0,
                   targets=("wq", "wk", "wv", "wo")) -> tuple[FedAlgorithm, dict]:
    """Flat federated LoRA: returns (FedAvg-over-adapters algorithm,
    initial adapter pytree). Drop both into the existing Simulator /
    build_round_fn / cross-silo managers — the round payload is the adapter
    tree only (reference parity: peft exchanges only adapter state_dicts).

    The frozen base rides in `ServerState.extra`: `alg.server_init` puts it
    there and every client reads it off the broadcast, so the round program
    takes the base as an ARGUMENT. A base closed over by the apply fn is
    baked into the program as a literal instead — at 1.2B parameters that
    is 2.4 GB of HLO text to lower, a second copy of the base in device
    memory, and minutes of compile.

    NOTE: the round engines DONATE their input server state, so after the
    first round both the adapters and the base arrays handed in here are
    consumed — the live ones are `out.server_state.params` / `.extra`
    (copy first, `jax.tree.map(jnp.array, tree)`, to keep a second
    handle)."""
    adapters = lora_init(rng, base_params, rank=rank, targets=targets)
    apply = model.apply
    if getattr(model, "has_counters", False):
        # what the model sows of itself (the expert layers' pairs and
        # fullest expert) rides the round's metrics beside the loss
        def apply(variables, x, *args, **kwargs):
            logits, sown = model.apply(variables, x, *args,
                                       mutable=[COUNTERS], **kwargs)
            return logits, fold_counters(sown[COUNTERS])

    def fedavg_over(base):
        # TrainArgs.compute_dtype as the Simulator path honors it
        # (simulator.py): bf16 runs the merged matmuls on the MXU while the
        # adapters/optimizer stay f32
        return make_fedavg(
            adapted_apply_fn(model, base, alpha, t.compute_dtype, apply), t)

    avg = fedavg_over(base_params)   # its server side never calls the apply

    def server_init(params, cfg=None):
        return avg.server_init(params, cfg).replace(extra=base_params)

    def client_update(bcast, shard, client_state, rng):
        return fedavg_over(bcast["extra"]).client_update(
            {**bcast, "extra": None}, shard, client_state, rng)

    return dataclasses.replace(avg, server_init=server_init,
                               client_update=client_update), adapters


def make_fedllm_seq_round(
    model: TransformerLM,
    base_params: Pytree,
    t: TrainArgs,
    mesh: Mesh,
    alpha: float = 16.0,
    client_axis: str = "silos",
    seq_axis: str = "seq",
    attn: str = "ring",
    inscan_quant: bool = False,
) -> Callable:
    """Long-context federated LoRA round over a (silos, seq) mesh.

    round_fn(server_state, base_params, data, ids, weights, rng)
        -> (server_state, metrics)
    where server_state.params is the ADAPTER pytree (replicated), base_params
    is the frozen base (replicated, passed explicitly so it can be donated /
    live once in HBM), data is laid out by `shard_fedllm_data`, ids/weights
    as in the flat engine.

    attn: "ring" (ppermute K/V rotation) or "ulysses" (all_to_all head
    scatter; needs n_heads % seq_size == 0).

    inscan_quant: the long-context 7B layout — `model` must be
    scan_layers=True and base_params the int8 tree (quant.quantize_tree_
    int8); the forward is quant.make_inscan_quant_apply with the
    sequence-parallel attention INSIDE the layer scan, so peak HBM stays
    int8 base + ONE dense block + remat checkpoints while the token
    dimension shards over `seq_axis`. This is the composition scale.py's
    module-level path cannot express (flax nn.scan rejects a collective
    inside the scanned block); the hand-written scan here can.
    """
    from .decode import engine_only, unserved

    # its own condition, not the decode path's: ring and ulysses attention
    # are full-causal over as many KV heads as heads, and the model is
    # rebuilt below from the dense fields
    lacking = unserved(model)
    why = engine_only(model)
    if why:
        lacking = lacking + [why + ": the block is rebuilt from the dense "
                             "fields"]
    if (model.norm_eps, model.rope_base) != (1e-6, 10000.0):
        lacking = lacking + [
            "another norm eps or rope base: the block is rebuilt from the "
            "dense fields"]
    if lacking:
        raise NotImplementedError(
            "the sequence-parallel round trains the dense block only; this "
            "model has: " + "; ".join(s.split(":")[0] for s in lacking))
    n_seq = mesh.shape[seq_axis]
    if attn == "ring":
        attn_fn = functools.partial(ring_attention, axis_name=seq_axis)
    elif attn == "ulysses":
        if model.n_heads % n_seq:
            raise ValueError(
                f"ulysses needs n_heads ({model.n_heads}) divisible by the "
                f"{seq_axis!r} axis size ({n_seq}); use attn='ring'")
        attn_fn = functools.partial(ulysses_attention, axis_name=seq_axis)
    else:
        raise ValueError(f"attn must be 'ring' or 'ulysses', got {attn!r}")
    if inscan_quant:
        from .quant import make_inscan_quant_apply

        if not model.scan_layers:
            raise ValueError(
                "inscan_quant=True needs a TransformerLM(scan_layers=True) "
                "model: the in-scan apply consumes the stacked "
                "'blocks' param layout (per-block keys would KeyError deep "
                "inside jit instead)")
        if not (isinstance(base_params, dict) and "blocks" in base_params):
            raise ValueError(
                "inscan_quant=True needs base_params from a scan_layers "
                "init (a top-level 'blocks' stack, optionally int8 via "
                f"quant.quantize_tree_int8); got keys "
                f"{sorted(base_params)[:6] if isinstance(base_params, dict) else type(base_params)}")
        inscan_apply = make_inscan_quant_apply(
            model.n_heads, attn_fn=attn_fn, alpha=alpha,
            dtype=jnp.dtype(t.compute_dtype))

        def sp_logits(base, a, x, off):
            return inscan_apply(base, a, x, pos_offset=off).astype(
                jnp.float32)
    else:
        # same architecture, sequence-parallel attention bound to the mesh
        # axis; compute_dtype honored and the adapters reached as on the
        # flat path (adapted_apply_fn)
        spmodel = TransformerLM(
            vocab_size=model.vocab_size, d_model=model.d_model,
            n_layers=model.n_layers, n_heads=model.n_heads, d_ff=model.d_ff,
            attn_fn=attn_fn)

        def sp_logits(base, a, x, off):
            return adapted_apply_fn(spmodel, base, alpha, t.compute_dtype)(
                {"params": a}, x, pos_offset=off)

    opt = optax.sgd(t.learning_rate,
                    momentum=t.momentum if t.momentum else None)

    spec_r = P()
    spec_c = P(client_axis)
    spec_ct = P(client_axis, None, seq_axis)   # [clients, seqs, tokens]

    def local_lora_sgd(base, adapters, shard, batch_idx, t_loc):
        """lax.scan local SGD on adapters; grads psum'd over seq per step."""
        opt_state = opt.init(adapters)
        off = jax.lax.axis_index(seq_axis) * t_loc

        def step(carry, idx):
            ad, s = carry
            batch = {k: v[idx] for k, v in shard.items()}

            def loss_sum(a):
                logits = sp_logits(base, a, batch["x"], off)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["y"])                       # [B, T_loc]
                m = batch["mask"][:, None]
                lsum = (ce * m).sum()
                correct = ((jnp.argmax(logits, -1) == batch["y"]) * m).sum()
                return lsum, correct

            (lsum, correct), grads = jax.value_and_grad(
                loss_sum, has_aux=True)(ad)
            # tokens in this step, across the whole ring
            cnt = jax.lax.psum(
                batch["mask"].sum() * t_loc, seq_axis)
            denom = jnp.maximum(cnt, 1.0)
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, seq_axis) / denom.astype(g.dtype),
                grads)
            lsum = jax.lax.psum(lsum, seq_axis)
            correct = jax.lax.psum(correct, seq_axis)
            updates, s = opt.update(grads, s, ad)
            ad = optax.apply_updates(ad, updates)
            return (ad, s), (lsum, correct, cnt)

        (adapters, _), (ls, cs, ns) = jax.lax.scan(
            step, (adapters, opt_state), batch_idx)
        return adapters, (ls.sum(), cs.sum(), ns.sum())

    def round_body(server_state: ServerState, base, data, ids, weights, rng):
        adapters0 = server_state.params
        shards = {k: jnp.take(v, ids, axis=0) for k, v in data.items()}
        shards = jax.lax.with_sharding_constraint(
            {"x": shards["x"], "y": shards["y"]},
            NamedSharding(mesh, spec_ct)) | {
            "mask": jax.lax.with_sharding_constraint(
                shards["mask"], NamedSharding(mesh, P(client_axis)))}
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(ids)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(spec_r, spec_r,
                      {"x": spec_ct, "y": spec_ct, "mask": spec_c},
                      spec_c, spec_c),
            out_specs=(spec_r, spec_r),
        )
        def block(ad0, base_l, sh, rg, w):
            ad0 = _localize(_localize(ad0, client_axis), seq_axis)
            base_l = _localize(_localize(base_l, client_axis), seq_axis)
            s_count = sh["y"].shape[1]          # sequences per client
            t_loc = sh["y"].shape[2]            # local token chunk
            bs = min(t.batch_size, s_count)

            def one_client(carry, inp):
                sh_i, rg_i, w_i = inp
                idx = make_batch_indices(rg_i, s_count, bs, t.epochs)
                ad, (lsum, correct, cnt) = local_lora_sgd(
                    base_l, ad0, sh_i, idx, t_loc)
                delta = tu.tree_sub(ad, ad0)
                wi = w_i.astype(jnp.float32)
                num = jax.tree.map(lambda a: a * wi, delta)
                live = (w_i > 0).astype(jnp.float32)
                return carry, (num, wi, (lsum * live, correct * live,
                                         cnt * live))

            _, (nums, ws, mets) = jax.lax.scan(one_client, None, (sh, rg, w))
            num = jax.lax.psum(jax.tree.map(lambda a: a.sum(0), nums),
                               client_axis)
            den = jax.lax.psum(ws.sum(), client_axis)
            agg = jax.tree.map(lambda a: a / jnp.maximum(den, 1e-12), num)
            # identical on every seq device already; pmean re-establishes
            # replication for the P() out_spec (numerical identity)
            agg = jax.lax.pmean(agg, seq_axis)
            summed = jax.lax.psum(
                jax.tree.map(lambda a: a.sum(0), mets), client_axis)
            return agg, summed

        agg, (lsum, correct, cnt) = block(
            adapters0, base, shards, rngs, weights)
        new_adapters = tu.tree_add(server_state.params, agg)
        new_state = server_state.replace(
            params=new_adapters, round=server_state.round + 1)
        n = jnp.maximum(cnt, 1.0)
        metrics = {"train_loss": lsum / n, "train_acc": correct / n,
                   "n_tokens": cnt}
        return new_state, metrics

    return jax.jit(round_body, donate_argnums=(0,))


def shard_fedllm_data(data: dict, mesh: Mesh, client_axis: str = "silos",
                      seq_axis: str = "seq") -> dict:
    """Lay out {"x": [N,S,T], "y": [N,S,T], "mask": [N,S]}: clients over the
    silo axis, token dimension over the seq axis (contiguous chunks — the
    layout ring_attention expects)."""
    tok = NamedSharding(mesh, P(client_axis, None, seq_axis))
    msk = NamedSharding(mesh, P(client_axis))
    return {
        "x": jax.device_put(jnp.asarray(data["x"], jnp.int32), tok),
        "y": jax.device_put(jnp.asarray(data["y"], jnp.int32), tok),
        "mask": jax.device_put(jnp.asarray(data["mask"], jnp.float32), msk),
    }
