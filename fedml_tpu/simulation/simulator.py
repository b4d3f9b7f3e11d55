"""Host-driven federated simulation loop.

The TPU analog of the reference simulators (reference:
simulation/simulator.py:26-238 SimulatorSingleProcess/MPI/NCCL and the
canonical FedAvgAPI.train loop, simulation/sp/fedavg/fedavg_api.py:66-125).
The host does only what cannot be traced: client sampling (seeded by round for
reference parity — fedavg_api.py:127-135), eval cadence, logging, checkpoints.
Everything else — local training of every sampled client, aggregation, the
server step — is ONE jitted XLA program per round (parallel/round.py).
"""
from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dp as dp_mod
from .. import security as sec_mod
from ..algorithms import build_algorithm
from ..compression import make_compression_transform
from ..config import BACKEND_XLA, Config
from ..core.algorithm import eval_step_fn
from ..data.fed_dataset import FedDataset
from ..data import loader as data_loader
from ..models import hub as model_hub
from ..ops import tree as tu
from ..parallel.mesh import make_mesh
from .. import schedule as lpt_sched
from ..parallel.round import build_block_fn, build_round_fn, shard_fed_data
from ..utils import enable_compilation_cache
from ..utils.events import recorder


def _compose(*fns):
    """Chain optional (upd, rng) -> upd transforms; None entries are skipped."""
    fns = [f for f in fns if f is not None]
    if not fns:
        return None

    def chained(upd, rng):
        for i, f in enumerate(fns):
            upd = f(upd, jax.random.fold_in(rng, i + 0x9A))
        return upd

    return chained


def _pad_test_batches(x: np.ndarray, y: np.ndarray, batch_size: int):
    n = x.shape[0]
    nb = (n + batch_size - 1) // batch_size
    pad = nb * batch_size - n
    xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]) if pad else x
    # y may carry trailing dims (sequence targets [N, T], multilabel [N, L])
    yp = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)]) if pad else y
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    rs = lambda a: a.reshape((nb, batch_size) + a.shape[1:])
    return rs(xp), rs(yp), rs(mask)


class Simulator:
    """fedml.run_simulation equivalent for backend in {"sp", "xla"}.

    backend="sp": single-device program (still jit, vmap over clients).
    backend="xla": shard_map over the `clients` mesh axis — one FL client
    (or a scanned set of clients) per chip.
    """

    def __init__(self, cfg: Config, dataset: Optional[FedDataset] = None,
                 model=None, mesh=None):
        self.cfg = cfg
        t = cfg.train_args
        enable_compilation_cache()   # before the first trace
        self.dataset = dataset if dataset is not None else data_loader.load(cfg)
        self.num_classes = self.dataset.num_classes

        self.model = model if model is not None else model_hub.create(
            cfg.model_args.model, self.num_classes, **cfg.model_args.extra
        )
        rng = jax.random.key(cfg.common_args.random_seed)
        self.params = model_hub.init_params(
            self.model, self.dataset.x_train.shape[2:], rng
        )

        use_mesh = cfg.comm_args.backend == BACKEND_XLA and len(jax.devices()) > 1
        if mesh is not None:
            self.mesh = mesh
        elif use_mesh:
            mapping = cfg.device_args.extra.get("mesh_mapping_file")
            if cfg.device_args.mesh_shape:
                self.mesh = make_mesh(cfg.device_args.mesh_shape)
            elif mapping:
                from ..parallel.mesh import mesh_from_file

                self.mesh = mesh_from_file(mapping)
            else:
                self.mesh = make_mesh({"clients": len(jax.devices())})
        else:
            self.mesh = None

        self.apply_fn = model_hub.mixed_precision_apply(
            self.model.apply, t.compute_dtype
        )
        self.alg = build_algorithm(
            t.federated_optimizer, self.apply_fn, t,
            t.client_num_in_total, t.client_num_per_round,
        )

        # -------- plugins: security, DP, compression (SURVEY.md §2.5/§2.4)
        self.attacker, self.defender = sec_mod.from_config(cfg)
        self.dp = dp_mod.from_config(cfg, counts=self.dataset.counts)
        comp_name = str(t.extra.get("compression", "none")).lower()
        comp_ratio = float(t.extra.get("compression_ratio", 0.05))
        if comp_name == "eftopk":
            # error feedback carries per-client residual state — it rides the
            # engine's client-state mechanism, not the stateless hook. The
            # defender's update transform moves inside the wrapper (before
            # sparsification) so the pipeline order matches every other
            # compressor: defender -> compress -> dp.
            from ..compression import wrap_algorithm_with_eftopk
            self.alg = wrap_algorithm_with_eftopk(
                self.alg, comp_ratio,
                pre_transform=self.defender.update_transform(),
            )
            post_update = _compose(self.dp.client_transform())
        else:
            comp = make_compression_transform(
                comp_name, comp_ratio, int(t.extra.get("quantize_bits", 8)),
            )
            post_update = _compose(
                self.defender.update_transform(), comp, self.dp.client_transform()
            )
        agg_full = sec_mod.build_server_pipeline(self.attacker, self.defender)
        from ..core.algorithm import FULL as _FULL
        self._use_full = agg_full is not None or self.alg.agg_mode == _FULL
        dp_server = self.dp.server_transform()
        dfs_post = self.defender.postprocess_agg()
        post_agg = None
        if dp_server is not None or dfs_post is not None:
            def post_agg(agg, ctx):  # noqa: E306
                if dfs_post is not None:
                    agg = dfs_post(agg, ctx)
                if dp_server is not None:
                    agg = dp_server(agg, jax.random.fold_in(ctx["rng"], 0xD9))
                return agg

        self._schedule = bool(t.extra.get("heterogeneity_schedule", True))
        group = int(t.extra.get("clients_per_device_parallel", 1))
        # run-health plane (ISSUE 3): per-client health stats ride the round
        # program's existing metrics transfer (default on — measured under
        # the telemetry budget; train_args.extra.health_stats=False opts
        # out of the IN-JIT stats only). The tracker itself is always on:
        # participation, round gauges, and straggler detection need no
        # device outputs, and observe_round accepts health=None.
        self._health_enabled = bool(t.extra.get("health_stats", True))
        from ..utils.health import HealthTracker

        self.health = HealthTracker.from_config(cfg)
        # opt-in live scrape surface (common_args.extra.metrics_port)
        from ..utils.prometheus import maybe_start_metrics_server

        self.metrics_exporter = maybe_start_metrics_server(cfg)
        # chaos plane (ISSUE 4): seeded client-fault injection runs INSIDE
        # the round/block programs (parallel/round.py) so the aggregate
        # reweights over survivors with no host round-trip; the spec is the
        # same one the comm stack's ChaosTransport consumes
        from ..comm.chaos import FaultSpec

        self.fault_spec = FaultSpec.from_config(cfg)
        # one kwargs dict drives BOTH engines: the per-round program and the
        # K-round scanned block program trace the identical round body
        self._round_kwargs = dict(
            mesh=self.mesh, group_size=group,
            aggregate_full=agg_full, postprocess_update=post_update,
            postprocess_agg=post_agg,
            num_real_clients=t.client_num_per_round,
            health_stats=self._health_enabled,
            client_dropout=(self.fault_spec.client_dropout
                            if self.fault_spec else 0.0),
            client_straggler=(self.fault_spec.client_straggler
                              if self.fault_spec else 0.0),
        )
        # ---- Parrot-scale cohort chunking (ISSUE 8): when cohort_chunk is
        # set, an m-client round streams through HBM-bounded chunk programs
        # (parallel/round.build_chunk_fns) with the partial aggregate riding
        # a donated carry — m is bounded by host RAM, not device memory.
        cc = int(t.extra.get("cohort_chunk", 0) or 0)
        self._cohort_chunk = cc
        self._ingest_prefetch = int(t.extra.get("ingest_prefetch", 1) or 0)
        self.chunk_fn = self.finalize_fn = self._make_carry = None
        if cc:
            d = self.mesh.devices.size if self.mesh is not None else 1
            if cc % d:
                raise ValueError(
                    f"train_args.cohort_chunk ({cc}) must be a multiple of "
                    f"the mesh size ({d}): a chunk splits into per-device "
                    "sub-batches")
            if group > 1 and (cc // d) % group:
                # a group that does not divide the per-device chunk would
                # change the scan's group boundaries vs the single-shot
                # program — the bitwise guarantee would silently degrade
                # to float tolerance (README "Scale-out simulation")
                raise ValueError(
                    f"train_args.clients_per_device_parallel ({group}) "
                    f"must divide the per-device chunk "
                    f"(cohort_chunk/mesh = {cc // d}): unaligned client "
                    "groups break chunked == single-shot bit-identity")
            if self._health_enabled:
                import logging

                logging.getLogger(__name__).info(
                    "cohort_chunk=%d: in-jit per-client health stats do not "
                    "ride chunked rounds (cosine-to-aggregate needs the "
                    "full update stack); participation/straggler tracking "
                    "stays on", cc)
            self._health_enabled = False
            self._round_kwargs["health_stats"] = False
            from ..parallel.round import build_chunk_fns

            self.chunk_fn, self.finalize_fn, self._make_carry = \
                build_chunk_fns(self.alg, **self._round_kwargs)
            self.round_fn = None
        else:
            self.round_fn = build_round_fn(self.alg, **self._round_kwargs)
        self.block_fn = None   # built lazily on the first blocked dispatch
        self.hook_state = sec_mod.init_pipeline_state(
            self.attacker, self.defender, self.params, t.client_num_per_round
        ) if agg_full is not None else None

        self.server_state = self.alg.server_init(self.params, cfg)
        if self.alg.client_state_init is not None:
            one = self.alg.client_state_init(self.params)
            self.client_states = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.dataset.num_clients,) + a.shape).copy(),
                one,
            )
        else:
            self.client_states = jnp.zeros((self.dataset.num_clients,))
        if self.mesh is not None:
            # pin replicated layouts up front: the round/chunk/finalize jit
            # caches key on input shardings, and uncommitted first-round
            # state would buy one throwaway compile per program before
            # settling on the layouts the programs themselves return
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            self.server_state = jax.device_put(self.server_state, rep)
            self.client_states = jax.device_put(self.client_states, rep)
            if self.hook_state is not None:
                self.hook_state = jax.device_put(self.hook_state, rep)

        raw = {
            "x": self.dataset.x_train,
            "y": self.dataset.y_train,
            "mask": self.dataset.mask_train,
        }
        # data-poisoning attacks mutate host arrays before upload (reference:
        # fedml_attacker.poison_data hook, client_trainer.py:32-38)
        raw = self.attacker.poison_dataset(raw, self.num_classes)
        counts = np.asarray(self.dataset.counts, np.float32)
        if self._cohort_chunk:
            # chunked rounds stream per-chunk cohort slices from HOST
            # memory (simulation/ingest.py): the full stacked dataset never
            # lands on device, and ghost-client mesh padding is unnecessary
            # because only sampled cohorts ever ship
            self._host_data = {k: np.asarray(v) for k, v in raw.items()}
            self.data = None
            from .ingest import IngestPipeline

            self._ingest = IngestPipeline(self._ingest_prefetch)
        else:
            self._host_data = None
            self._ingest = None
            if self.mesh is not None:
                # the stacked client axis must divide the mesh; pad with
                # zero-mask ghost clients (never sampled — sample_clients
                # draws < num_clients)
                d = self.mesh.devices.size
                pad = (-raw["x"].shape[0]) % d
                if pad:
                    raw = {
                        k: np.concatenate(
                            [v, np.zeros((pad,) + v.shape[1:], v.dtype)]
                        ) for k, v in raw.items()
                    }
                    counts = np.concatenate([counts, np.zeros(pad, np.float32)])
            self.data = shard_fed_data(raw, self.mesh)
        self.counts = jnp.asarray(counts)
        # Parrot cost model (ISSUE 8 leg 3): dispatch wall times feed a
        # runtime~samples fit; once trustworthy, LPT costs switch from raw
        # sample counts to predicted runtimes (schedule.CostModel)
        self._cost_model = lpt_sched.CostModel.from_config(
            t.extra.get("cost_model"),
            {i: int(c) for i, c in
             enumerate(np.asarray(self.dataset.counts))})
        # the first dispatch's wall time is dominated by the XLA compile
        # (orders of magnitude above steady state) — recording it would
        # poison the per-client empirical means and the fit error
        self._cold_dispatch = True

        xb, yb, mb = _pad_test_batches(
            self.dataset.x_test, self.dataset.y_test, max(t.batch_size, 64)
        )
        self._test = (jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb))
        from ..core.algorithm import make_eval_fn

        # task-aware: segmentation evaluates through the whole-set
        # confusion-matrix evaluator so mIoU rides the eval row (FedSeg
        # parity — the reference server evaluates mIoU every round).
        # track_jit: eval retraces surface as xla.compiles/retraces.eval_fn
        # like the round/block programs (ISSUE 2 always-on retrace metric)
        from ..utils.metrics import track_jit

        self._eval = track_jit(
            make_eval_fn(self.apply_fn, t.extra.get("task"),
                         self.num_classes), "eval_fn")
        # device-memory ledger (ISSUE 17): the simulator's resident trees
        # — global params and the per-client optimizer/state stack — so
        # `report`'s xla.ledger.* rows account for training HBM too
        from ..utils import xla_ledger as _ledger

        _ledger.register_buffers("fed_params", self.params)
        _ledger.register_buffers("client_states", self.client_states)
        self.history: list[dict] = []

    # reference parity: sampling seeded by round index (fedavg_api.py:127-135
    # does np.random.seed(round_idx); a LOCAL RandomState(round_idx) draws
    # the bit-identical ids — same MT19937 seeding — without perturbing the
    # process-global numpy RNG that chaos/async/data code shares)
    def sample_clients(self, round_idx: int) -> np.ndarray:
        t = self.cfg.train_args
        n, m = self.dataset.num_clients, t.client_num_per_round
        if n == m:
            return np.arange(m, dtype=np.int32)
        rs = np.random.RandomState(round_idx)
        return np.sort(rs.choice(range(n), m, replace=False)).astype(np.int32)

    def _pad_only(self, ids: np.ndarray):
        """Pad sampled ids to a multiple of the mesh size — of the cohort
        chunk when chunking, so every chunk program sees full static shapes
        — with zero-weight duplicates so shard shapes stay static. Returns
        (padded_ids, weights, pad)."""
        weights = np.asarray(self.counts)[ids].astype(np.float32)
        mult = self._cohort_chunk or (
            self.mesh.devices.size if self.mesh is not None else 0)
        if not mult:
            return ids, weights, 0
        pad = (-len(ids)) % mult
        if pad:
            # pad with a duplicate of an already-sampled client (weight 0):
            # its recompute is identical, so the client-state scatter-back is a
            # harmless rewrite — padding with id 0 would corrupt client 0's
            # persistent state (SCAFFOLD c_i / FedDyn h_i) on unsampled rounds
            ids = np.concatenate([ids, np.full(pad, ids[0], np.int32)])
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
        return ids, weights, pad

    def _lpt_applies(self, weights: np.ndarray, pad: int) -> bool:
        """Whether one round's padded id row gets the balanced-LPT permute.
        FULL-mode aggregation slices the real clients back out as a prefix
        (round.py call_full, num_real_clients); a permutation that moves pad
        duplicates into that prefix would silently drop real updates — skip
        scheduling whenever both padding and FULL hooks are in play."""
        if self.mesh is None:
            return False
        d = self.mesh.devices.size
        schedulable = pad == 0 or not self._use_full
        varied = (len(np.unique(weights)) > 1
                  or (self._cost_model is not None
                      and self._cost_model.engaged()))
        return bool(self._schedule and schedulable and len(weights) > d
                    and varied)

    def _sched_costs(self, ids: np.ndarray, weights: np.ndarray) -> np.ndarray:
        """Per-slot LPT costs for one padded id row: raw sample counts
        (== weights) until the runtime cost model engages, then predicted
        per-client runtimes (Parrot's heterogeneity-aware switch —
        schedule.CostModel). Pad duplicates keep cost 0 so the scheduler
        never treats them as load."""
        cm = self._cost_model
        if cm is None or not cm.engaged():
            return weights
        costs = cm.predict_costs(ids)
        return np.where(weights > 0, costs, 0.0).astype(float)

    def _record_dispatch(self, ids, weights, duration_s: float) -> None:
        """The wall-time recording hook feeding the cost model: one
        dispatch covering this id row took duration_s (pad duplicates
        excluded — their recompute is not schedulable load). The cold
        dispatch (jit compile riding the wall clock) is dropped."""
        if self._cost_model is None:
            return
        if self._cold_dispatch:
            return
        real = np.asarray(ids)[np.asarray(weights) > 0]
        self._cost_model.record_dispatch(real.tolist(), duration_s)
        # refresh the fit + fed.cost_model.* gauges every observation, not
        # only when the mesh scheduler consults engaged(): a mesh-less run
        # still fits and exports (LPT placement is mesh-only, but the
        # estimator must be observable wherever it records)
        self._cost_model.engaged()

    def _pad_ids(self, ids: np.ndarray):
        """Pad sampled ids to a multiple of the mesh size with zero-weight
        duplicates so shard_map shapes stay static, then balance per-device
        load with the Parrot scheduler (reference:
        FedAVGAggregator.generate_client_schedule, fedavg_seq:126-187 —
        uniform chunks would put all heavy clients on one chip when the
        dataset is skewed; balanced LPT permutes clients among the equal-size
        device slots so per-chip useful-sample load is even)."""
        ids, weights, pad = self._pad_only(ids)
        if self._lpt_applies(weights, pad):
            blocks = lpt_sched.balanced_lpt(self._sched_costs(ids, weights),
                                            self.mesh.devices.size)
            perm = np.concatenate([np.asarray(b, int) for b in blocks])
            ids, weights = ids[perm], weights[perm]
        return ids, weights

    def _schedule_block(self, rounds):
        """The host half of round-block execution: the [K, m] id/weight
        schedule for a block of rounds. Per-round seeded sampling and mesh
        padding run exactly as `_pad_ids` (reference parity is bit-for-bit),
        then ONE vectorized balanced-LPT pass (schedule.balanced_lpt_block)
        permutes every schedulable row at once — the host's only remaining
        per-round job, amortized to one numpy pass per block."""
        trips = [self._pad_only(self.sample_clients(r)) for r in rounds]
        ids = np.stack([i for i, _, _ in trips])
        weights = np.stack([w for _, w, _ in trips])
        rows = np.flatnonzero([self._lpt_applies(w, p) for _, w, p in trips])
        if rows.size:
            costs = np.stack([self._sched_costs(ids[i], weights[i])
                              for i in rows])
            perms = lpt_sched.balanced_lpt_block(
                costs, self.mesh.devices.size)
            ids[rows] = np.take_along_axis(ids[rows], perms, axis=1)
            weights[rows] = np.take_along_axis(weights[rows], perms, axis=1)
        return ids, weights

    def run_round(self, round_idx: int) -> dict:
        if self._cohort_chunk:
            return self._run_round_chunked(round_idx)
        # the host's share of a round, by part (PERF.md section 3): sample,
        # dispatch and observe are the host's own time, fetch is the one
        # wait on the device
        with recorder.span("fed.round.sample", round=round_idx):
            ids, weights = self._pad_ids(self.sample_clients(round_idx))
            rng = jax.random.fold_in(
                jax.random.key(self.cfg.common_args.random_seed), round_idx
            )
            ids_dev, weights_dev = jnp.asarray(ids), jnp.asarray(weights)
        t0 = time.perf_counter()
        with recorder.span("train", round=round_idx):
            with recorder.span("fed.round.dispatch", round=round_idx):
                out = self.round_fn(
                    self.server_state, self.client_states, self.data,
                    ids_dev, weights_dev, rng, self.hook_state,
                )
            with recorder.span("fed.round.fetch", round=round_idx):
                fetched = jax.device_get(out.metrics)
        # the per-client health arrays rode the SAME transfer as the scalar
        # metrics; peel them off before the history row is float-mapped
        health = fetched.pop("health", None)
        faults = fetched.pop("faults", None)
        metrics = jax.tree.map(float, fetched)
        self.server_state = out.server_state
        self.client_states = out.client_states
        self.hook_state = out.hook_state
        dur = time.perf_counter() - t0
        self._observe_round(round_idx, ids, weights, health, faults, dur,
                            metrics)
        return metrics

    def _observe_round(self, round_idx: int, ids, weights, health, faults,
                       dur: float, metrics: dict) -> None:
        """What the host does with a finished round before the next one:
        health accounting, the cost model, the DP accountant."""
        with recorder.span("fed.round.observe", round=round_idx):
            self.health.observe_round(round_idx, ids, weights, health,
                                      duration_s=dur, faults=faults)
            self._record_dispatch(ids, weights, dur)
            self._cold_dispatch = False
            self.dp.step_round()
            if self.dp.enabled and self.dp.accountant is not None:
                metrics["dp_epsilon"] = self.dp.get_epsilon()

    # ------------------------------------------- chunked cohort execution
    def _chunk_plan(self, ids: np.ndarray, weights: np.ndarray):
        """Split the padded, scheduled [m] id row into per-device/per-chunk
        sub-batches: chunk j takes rows [k*m_d + j*c, ..+c) of every device
        block k, so each device walks ITS schedule slice in order and the
        per-device accumulation order matches the single-shot program —
        the bit-identity invariant (parallel/round.chunk_body)."""
        m = len(ids)
        d = self.mesh.devices.size if self.mesh is not None else 1
        c = self._cohort_chunk // d
        m_d = m // d
        plan = []
        for j in range(m // self._cohort_chunk):
            rows = np.concatenate([
                np.arange(k * m_d + j * c, k * m_d + (j + 1) * c)
                for k in range(d)])
            plan.append((j, ids[rows], weights[rows]))
        return plan, c

    def _chunk_thunk(self, cids: np.ndarray, cw: np.ndarray):
        """One ingest unit: host-gather the chunk's client rows, ship them
        client-sharded. Runs on the ingest pipeline's worker thread."""
        def put():
            chunk = {k: v[cids] for k, v in self._host_data.items()}
            nbytes = sum(a.nbytes for a in chunk.values())
            dev = (shard_fed_data(chunk, self.mesh),
                   jnp.asarray(cids), jnp.asarray(cw))
            return dev, nbytes
        return put

    def _dispatch_chunked(self, round_idx: int):
        """Dispatch one chunk-streamed round — nothing here blocks on the
        device: chunk k+1's gather+transfer overlaps chunk k's compute
        (IngestPipeline), the partial aggregate rides the donated carry,
        and finalize closes the round. Returns (ids, weights, RoundOutput)."""
        with recorder.span("fed.round.sample", round=round_idx):
            ids, weights = self._pad_ids(self.sample_clients(round_idx))
            rng = jax.random.fold_in(
                jax.random.key(self.cfg.common_args.random_seed), round_idx)
            plan, c_local = self._chunk_plan(ids, weights)
        with recorder.span("fed.round.dispatch", round=round_idx):
            chunk_struct = {
                k: jax.ShapeDtypeStruct(
                    (len(plan[0][1]),) + v.shape[1:], v.dtype)
                for k, v in self._host_data.items()}
            carry = self._make_carry(self.server_state, self.client_states,
                                     ids, chunk_struct)
            thunks = [self._chunk_thunk(cids, cw) for _, cids, cw in plan]
            for (j, _, _), (cdata, cids_dev, cw_dev) in zip(
                    plan, self._ingest.stream(thunks)):
                carry = self.chunk_fn(
                    carry, self.server_state, cdata, cids_dev, cw_dev, rng,
                    jnp.asarray(j * c_local, jnp.int32))
            out = self.finalize_fn(
                self.server_state, carry, jnp.asarray(ids),
                jnp.asarray(weights), rng, self.hook_state)
        self.server_state = out.server_state
        self.client_states = out.client_states
        self.hook_state = out.hook_state
        return ids, weights, out

    def _run_round_chunked(self, round_idx: int) -> dict:
        t0 = time.perf_counter()
        with recorder.span("train", round=round_idx) as sp:
            ids, weights, out = self._dispatch_chunked(round_idx)
            sp.meta["chunks"] = len(ids) // self._cohort_chunk
            with recorder.span("fed.round.fetch", round=round_idx):
                fetched = jax.device_get(out.metrics)
        faults = fetched.pop("faults", None)
        metrics = jax.tree.map(float, fetched)
        dur = time.perf_counter() - t0
        # chunked rounds run the in-jit health stats off (see __init__);
        # participation/straggler accounting still observes every round
        self._observe_round(round_idx, ids, weights, None, faults, dur,
                            metrics)
        return metrics

    def _eval_dispatch(self):
        """Enqueue the test-set eval program; returns un-materialized device
        values (JAX async dispatch — the caller fetches them later, so the
        blocked driver can keep training blocks in flight behind an eval)."""
        return self._eval(self.server_state.params, *self._test)

    @staticmethod
    def _eval_finish(m) -> dict:
        m = jax.device_get(m)
        out = {"test_loss": float(m["loss"]), "test_acc": float(m["acc"])}
        if "miou" in m:                    # segmentation task head
            out["test_miou"] = float(m["miou"])
        return out

    def evaluate(self) -> dict:
        with recorder.span("eval"):
            return self._eval_finish(self._eval_dispatch())

    # ---------------------------------------------------- checkpoint/resume
    # (beyond the reference: a killed reference run restarts from round 0 —
    # SURVEY.md §5.4; here all cross-round state round-trips through orbax)
    def save(self, ckpt_dir: str, keep: Optional[int] = 3) -> str:
        from ..utils import checkpoint as ckpt

        rounds_done = len(self.history)
        if rounds_done == 0:
            raise ValueError(
                "nothing to checkpoint: no rounds have completed (a "
                "round_-1 directory would be invisible to restore)")
        return ckpt.save_checkpoint(
            ckpt_dir, rounds_done - 1, self.server_state,
            client_states=self.client_states, hook_state=self.hook_state,
            history=self.history, keep=keep)

    def restore(self, ckpt_dir: str) -> int:
        """Load the latest checkpoint; returns the next round to run.
        The sampler is round-seeded and the DP accountant is fast-forwarded,
        so the resumed run continues exactly where the dead one stopped."""
        from ..utils import checkpoint as ckpt

        r, server, clients, hook, history = ckpt.restore_checkpoint(
            ckpt_dir, self.server_state, self.client_states, self.hook_state)
        self.server_state = server
        if clients is not None:
            self.client_states = clients
        if hook is not None:
            self.hook_state = hook
        self.history = list(history)
        rounds_done = r + 1
        if self.dp.enabled and self.dp.accountant is not None:
            # the accountant must reflect exactly the restored number of
            # compositions — whether this instance is fresh (fast-forward)
            # or live and rolling BACK to an earlier checkpoint
            self.dp.accountant.steps = rounds_done
        return rounds_done

    # ------------------------------------------------------------ run loop
    def _eval_due(self, r: int, rounds: int) -> bool:
        f = self.cfg.validation_args.frequency_of_the_test
        return bool(f) and (r % f == 0 or r == rounds - 1)

    @staticmethod
    def _ckpt_due(r: int, rounds: int, checkpoint_dir, checkpoint_every) -> bool:
        return checkpoint_dir is not None and bool(checkpoint_every) and (
            (r + 1) % checkpoint_every == 0 or r == rounds - 1)

    def _publish_model(self, r: int, params) -> None:
        """Aggregated-model publish (reference: the aggregator calls
        mlops.log_aggregated_model_info every round —
        core/mlops/__init__.py:388); no-op unless an artifact store is
        configured via mlops.init/set_artifact_store. Degrade, don't die:
        like the telemetry sinks, a store hiccup must not kill a long
        training run."""
        from .. import mlops

        try:
            mlops.log_aggregated_model_info(r, params)
        except Exception as e:  # noqa: BLE001
            import logging

            logging.getLogger(__name__).warning(
                "round-%d model-artifact publish failed (continuing): "
                "%s: %s", r, type(e).__name__, e)

    def _run_one(self, r: int, rounds: int) -> None:
        """One host-synchronous round: train, eval on cadence, log, publish."""
        row = {"round": r, **self.run_round(r)}
        if self._eval_due(r, rounds):
            row.update(self.evaluate())
        recorder.log(row)
        self.history.append(row)
        self._publish_model(r, self.server_state.params)

    # ------------------------------------------------- round-block pipeline
    def _dispatch_block(self, blk: list[int], base_rng, rounds: int):
        """Enqueue one K-round block program plus whatever must read its
        output params (eval, artifact snapshot) BEFORE the next dispatch
        donates them. Nothing here blocks on the device."""
        if self._cohort_chunk:
            # chunked + blocked: every round in the block streams its chunk
            # programs (all async-dispatched — the carry chain and donation
            # keep the device busy) and the block defers ALL metric fetches
            # to drain time. Same programs, same keys as per-round chunked
            # mode, so blocked == per-round stays bit-identical.
            t0 = time.perf_counter()
            ids_l, w_l, mets = [], [], []
            for r in blk:
                ids_r, w_r, out_r = self._dispatch_chunked(r)
                ids_l.append(ids_r)
                w_l.append(w_r)
                mets.append(out_r.metrics)
            ids, weights, metrics = np.stack(ids_l), np.stack(w_l), mets
        else:
            if self.block_fn is None:
                self.block_fn = build_block_fn(self.alg, **self._round_kwargs)
            ids, weights = self._schedule_block(blk)
            t0 = time.perf_counter()
            out = self.block_fn(
                self.server_state, self.client_states, self.data,
                jnp.asarray(ids), jnp.asarray(weights), base_rng,
                jnp.asarray(blk, dtype=jnp.int32), self.hook_state,
            )
            self.server_state = out.server_state
            self.client_states = out.client_states
            self.hook_state = out.hook_state
            metrics = out.metrics
        eval_out = (self._eval_dispatch()
                    if self._eval_due(blk[-1], rounds) else None)
        # per-round publishes degrade to one per block in blocked mode
        # (intermediate params never materialize); snapshot on device so the
        # next block's donation can't free the buffers under the store
        from .. import mlops

        snap = (jax.tree.map(jnp.copy, self.server_state.params)
                if mlops.artifact_store() is not None else None)
        return (blk, ids, weights, metrics, eval_out, snap, t0)

    def _drain_block(self, pending) -> None:
        """Materialize one dispatched block: ONE host transfer for the
        stacked [K] metrics, then per-round history rows exactly as the
        per-round driver writes them (DP accountant advanced K times, each
        round's epsilon computed at its own composition count). The block's
        "train" span covers dispatch→materialization — the async dispatch
        returns in microseconds, so timing the dispatch alone would report
        near-zero per-round durations to the sinks."""
        blk, ids, weights, metrics, eval_out, snap, t0 = pending
        if isinstance(metrics, list):
            # chunked dispatch returns one metrics pytree PER ROUND; stack
            # them into the same [K]-leading layout the block program emits
            fetched = [jax.device_get(x) for x in metrics]
            m = jax.tree.map(lambda *xs: np.stack(xs), *fetched)
        else:
            m = jax.device_get(metrics)
        block_s = time.perf_counter() - t0
        # stacked [K, m] health arrays rode the block's single transfer;
        # peel them off before the scalar rows are built, then feed the
        # tracker one round at a time (same cadence as per-round mode, with
        # the block's wall time amortized for straggler detection)
        health = m.pop("health", None)
        faults = m.pop("faults", None)
        recorder.log_block_span("train", blk, block_s)
        for j, r in enumerate(blk):
            row = {"round": r}
            row.update({k: float(v[j]) for k, v in m.items()})
            h_j = ({k: v[j] for k, v in health.items()}
                   if health is not None else None)
            f_j = ({k: v[j] for k, v in faults.items()}
                   if faults is not None else None)
            self.health.observe_round(
                r, ids[j], weights[j], h_j,
                duration_s=block_s / max(len(blk), 1), faults=f_j)
            self._record_dispatch(ids[j], weights[j],
                                  block_s / max(len(blk), 1))
            self.dp.step_round()
            if self.dp.enabled and self.dp.accountant is not None:
                row["dp_epsilon"] = self.dp.get_epsilon()
            if eval_out is not None and r == blk[-1]:
                # keep the "eval" span series alive in blocked mode: the
                # program was async-dispatched back in _dispatch_block, so
                # what's measurable here is the host's materialization wait
                # (flagged block:true like the train rows)
                te = time.perf_counter()
                row.update(self._eval_finish(eval_out))
                recorder.log_block_span("eval", [r],
                                        time.perf_counter() - te)
            recorder.log(row)
            self.history.append(row)
        # the whole first block rode the compile: only after it drains do
        # dispatch times become steady-state observations
        self._cold_dispatch = False
        if snap is not None:
            self._publish_model(blk[-1], snap)

    def _run_blocked(self, start: int, rounds: int, block_size: int,
                     checkpoint_dir, checkpoint_every) -> None:
        """Pipelined round-block driver: K rounds per XLA dispatch, block
        i+1 dispatched before block i's metrics are fetched (JAX async
        dispatch keeps the device busy across the host's schedule/LPT work).
        Blocks never span an eval/checkpoint round, so blocked and per-round
        runs produce identical history; ragged tails (cadence not a multiple
        of K, end of horizon) fall back to the per-round program instead of
        minting one block compile per distinct length."""
        from collections import deque

        t = self.cfg.train_args
        depth = max(1, int(t.extra.get("block_pipeline_depth", 2) or 1))
        # a barrier cadence shorter than the block size means no block ever
        # fills — the whole run would silently execute the per-round program
        # at 1x while the config claims blocked mode; say so once up front
        cadences = [c for c in (
            self.cfg.validation_args.frequency_of_the_test,
            checkpoint_every if checkpoint_dir is not None else 0,
        ) if c]
        if cadences and min(cadences) < block_size:
            import logging

            logging.getLogger(__name__).warning(
                "rounds_per_block=%d exceeds the eval/checkpoint cadence "
                "(%d): blocks between barriers never fill, so most or all "
                "rounds will run the per-round program; lower "
                "rounds_per_block or raise the cadence to get blocked "
                "throughput", block_size, min(cadences))
        base_rng = jax.random.key(self.cfg.common_args.random_seed)
        pending: deque = deque()

        def drain_all():
            while pending:
                self._drain_block(pending.popleft())

        blk: list[int] = []
        for r in range(start, rounds):
            blk.append(r)
            barrier = self._eval_due(r, rounds) or self._ckpt_due(
                r, rounds, checkpoint_dir, checkpoint_every)
            if not barrier and len(blk) < block_size:
                continue
            if len(blk) == block_size:
                pending.append(self._dispatch_block(blk, base_rng, rounds))
                while len(pending) >= depth:
                    self._drain_block(pending.popleft())
            else:
                drain_all()
                for rr in blk:
                    self._run_one(rr, rounds)
            blk = []
            if self._ckpt_due(r, rounds, checkpoint_dir, checkpoint_every):
                drain_all()
                self.save(checkpoint_dir)
        if blk:   # ragged tail with no barrier at the horizon end
            drain_all()
            for rr in blk:
                self._run_one(rr, rounds)
        drain_all()

    def run(self, num_rounds: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0) -> list[dict]:
        t = self.cfg.train_args
        rounds = num_rounds if num_rounds is not None else t.comm_round
        start = 0
        if checkpoint_dir is not None:
            from ..utils.checkpoint import latest_round

            if latest_round(checkpoint_dir) is not None:
                start = self.restore(checkpoint_dir)
        block_size = max(1, int(t.extra.get("rounds_per_block", 1) or 1))
        if block_size > 1:
            self._run_blocked(start, rounds, block_size,
                              checkpoint_dir, checkpoint_every)
        else:
            for r in range(start, rounds):
                self._run_one(r, rounds)
                if self._ckpt_due(r, rounds, checkpoint_dir,
                                  checkpoint_every):
                    self.save(checkpoint_dir)
        from ..utils.sinks import flush_sinks

        flush_sinks()  # ship any buffered telemetry (BrokerLogSink batches)
        return self.history


def run_simulation(cfg: Config, dataset=None, model=None) -> list[dict]:
    # config-driven checkpointing: train_args.extra.checkpoint_dir enables
    # save+auto-resume (every round by default; checkpoint_every overrides)
    ckpt_dir = cfg.train_args.extra.get("checkpoint_dir")
    every = int(cfg.train_args.extra.get("checkpoint_every", 1) or 0)
    return Simulator(cfg, dataset, model).run(
        checkpoint_dir=ckpt_dir, checkpoint_every=every if ckpt_dir else 0)
