"""FedMLRunner — single dispatch from (training_type, backend, scenario,
role) to a runtime.

(reference: python/fedml/runner.py:19-181 FedMLRunner routing
simulation / cross_silo / cross_device / cross_cloud / serving to per-mode
runner classes, each with a .run(); roles come from args.role.)

Modes here:
- simulation + horizontal:       Simulator            (sp / xla backends)
- simulation + hierarchical:     Simulator over a (silos, intra) mesh is
                                 the XLA shape; the runner uses the flat
                                 Simulator when the mesh isn't 2-D
- simulation + async:            AsyncSimulator (train_args.extra.async)
- cross_silo, role=server:       FedServerManager (+SecAgg variant)
- cross_silo, role=client:       FedClientManager + SiloTrainer; with
                                 scenario=hierarchical the client's
                                 SiloTrainer gets an intra-silo device mesh
                                 (single-host all-in-one composition is
                                 cross_silo.run_hierarchical, called
                                 directly rather than through this runner)
- cross_device, role=server:     CrossDeviceServer
- fa (train_args.extra.fa_task): FASimulator
- centralized baseline:          CentralizedTrainer (training_type
                                 'centralized')
"""
from __future__ import annotations

from typing import Any, Optional

from .config import (
    Config, SCENARIO_HIERARCHICAL, TRAINING_TYPE_CENTRALIZED,
    TRAINING_TYPE_CROSS_DEVICE, TRAINING_TYPE_CROSS_SILO,
    TRAINING_TYPE_SIMULATION,
)

Pytree = Any


class FedMLRunner:
    """(reference: runner.py:19) args/config -> runtime with .run()."""

    def __init__(self, cfg: Config, dataset=None, model=None,
                 role: str = "server", rank: int = 0,
                 transport: Optional[str] = None, **kw):
        from .utils import enable_compilation_cache

        self.cfg = cfg
        enable_compilation_cache()   # before any runtime's first trace
        tt = cfg.common_args.training_type
        fa_task = cfg.train_args.extra.get("fa_task")
        if fa_task:
            self.runner = self._init_fa(fa_task, dataset, **kw)
        elif tt == TRAINING_TYPE_SIMULATION:
            self.runner = self._init_simulation(dataset, model, **kw)
        elif tt == TRAINING_TYPE_CROSS_SILO:
            self.runner = self._init_cross_silo(
                dataset, model, role, rank, transport, **kw)
        elif tt == TRAINING_TYPE_CROSS_DEVICE:
            self.runner = self._init_cross_device(
                dataset, model, role, rank, transport, **kw)
        elif tt == TRAINING_TYPE_CENTRALIZED:
            from .centralized import CentralizedTrainer

            self.runner = CentralizedTrainer(cfg, dataset, model)
        else:
            raise ValueError(
                f"no runner for training_type={tt!r} (reference parity: "
                "simulation / cross_silo / cross_device / centralized; "
                "cross_cloud is covered by cross_silo over gRPC across "
                "regions)")

    # ------------------------------------------------------------ simulation
    def _init_simulation(self, dataset, model, **kw):
        t = self.cfg.train_args
        if t.extra.get("async") or t.extra.get("async_mode"):
            if kw:
                raise ValueError(
                    f"async simulation does not accept {sorted(kw)} (the "
                    "event loop is host-driven, single-device)")
            from .simulation.async_simulator import AsyncSimulator

            return AsyncSimulator(self.cfg, dataset, model)
        from .simulation.simulator import Simulator

        return Simulator(self.cfg, dataset, model, **kw)

    def _init_fa(self, fa_task, dataset, **kw):
        from .fa import FASimulator

        if dataset is None:
            raise ValueError("FA mode needs `dataset`: a list of per-client "
                             "value collections")
        return FASimulator(
            fa_task, dataset,
            client_num_per_round=self.cfg.train_args.client_num_per_round,
            num_rounds=self.cfg.train_args.comm_round, **kw)

    # ------------------------------------------------------------ cross-silo
    def _init_cross_silo(self, dataset, model, role, rank, transport, **kw):
        import jax
        import numpy as np

        from .comm import FedCommManager, create_transport
        from .models import hub

        cfg = self.cfg
        t = cfg.train_args
        backend = transport or cfg.comm_args.extra.get("transport", "loopback")
        ip_table = cfg.comm_args.grpc_ipconfig_path or None
        run_id = cfg.comm_args.extra.get("run_id", "cs")
        # robustness stack (ISSUE 4): chaos injection + reliable delivery
        # ride the same config keys every runtime reads. The wire codec
        # plane (ISSUE 14) rides comm_args.comm_codec on BOTH roles —
        # delta frames decode against the receiving end's anchor state, so
        # a one-sided codec would be a loud decode error, not savings.
        codec_cfg = cfg.comm_args.extra.get("comm_codec")
        rel = dict(chaos=cfg.common_args.extra.get("chaos"),
                   comm_retry=cfg.common_args.extra.get("comm_retry"),
                   comm_codec=codec_cfg)
        if backend == "grpc":
            tr = create_transport(backend, rank, ip_table=ip_table, **rel)
        else:
            # loopback AND broker are namespaced by run_id — the broker is
            # store-and-forward, so sharing a default namespace would leak
            # one run's frames into the next
            tr = create_transport(backend, rank, run_id=run_id, **rel)
        comm = FedCommManager(tr, rank)
        secagg = bool(t.extra.get("secagg"))
        client_ids = list(range(1, t.client_num_in_total + 1))
        # durability knobs (ISSUE 10): round-boundary checkpoint/resume on
        # the server, silence watchdog + heartbeats on the client. Same
        # checkpoint_dir/checkpoint_every keys the Simulator reads;
        # validated at config load.
        ck_every = t.extra.get("checkpoint_every")
        ckpt_kw = dict(
            checkpoint_dir=t.extra.get("checkpoint_dir"),
            # an EXPLICIT 0 means "no cadence checkpoints" (config.py
            # validates >= 0; _ckpt_due treats 0 as off) — `or 1` here
            # would silently re-enable what the operator disabled
            checkpoint_every=1 if ck_every is None else int(ck_every),
            checkpoint_keep=int(t.extra.get("checkpoint_keep", 3)),
            resume=bool(t.extra.get("resume")),
        )

        if role == "server":
            if model is None or "input_shape" not in kw:
                raise ValueError("cross-silo server needs `model` and "
                                 "input_shape=...")
            params = jax.tree.map(np.asarray, hub.init_params(
                model, kw.pop("input_shape"),
                jax.random.key(cfg.common_args.random_seed)))
            if secagg:
                from .cross_silo import SecAggServerManager

                return SecAggServerManager(
                    comm, client_ids=client_ids, init_params=params,
                    num_rounds=t.comm_round,
                    round_timeout=t.extra.get("round_timeout"),
                    **ckpt_kw, **kw)
            from .cross_silo import FedServerManager

            return FedServerManager(
                comm, client_ids=client_ids, init_params=params,
                num_rounds=t.comm_round,
                client_num_per_round=t.client_num_per_round,
                round_timeout=t.extra.get("round_timeout"),
                quorum_frac=float(t.extra.get("quorum_frac", 1.0)),
                liveness_timeout_s=t.extra.get("liveness_timeout_s"),
                max_rearms=int(t.extra.get("max_rearms", 5)),
                **ckpt_kw, **kw)

        # role == client: rank is the client id (1-based)
        if dataset is None or model is None:
            raise ValueError("cross-silo client needs `dataset`=(x, y) and "
                             "`model`")
        from .cross_silo import SiloTrainer

        x, y = dataset
        mesh = kw.pop("mesh", None)
        if cfg.common_args.scenario == SCENARIO_HIERARCHICAL and mesh is None:
            from .cross_silo.hierarchical import silo_mesh

            mesh = silo_mesh(jax.devices())
        trainer = SiloTrainer(model.apply, t, x, y, mesh=mesh, seed=rank)
        if secagg:
            from .cross_silo import SecAggClientManager

            # quantize-then-mask (ISSUE 14): lossy sparsify BEFORE the
            # shared field scale + mask; the wire leg (field_pack) is
            # attached to the transport above
            return SecAggClientManager(
                comm, rank, trainer, num_clients=len(client_ids),
                client_ids=client_ids,
                premask_ratio=(codec_cfg or {}).get("secagg_premask_ratio"),
                **kw)
        from .cross_silo import FedClientManager
        from .dp import make_upload_dp

        # a resumable server implies re-attaching clients (they must
        # re-announce to the restarted incarnation); `reattach` overrides
        return FedClientManager(
            comm, rank, trainer,
            server_timeout_s=t.extra.get("server_timeout_s"),
            reattach=bool(t.extra.get("reattach", t.extra.get("resume"))),
            heartbeat_s=t.extra.get("heartbeat_s"),
            dp_upload=make_upload_dp(cfg, seed=rank), **kw)

    # ---------------------------------------------------------- cross-device
    def _init_cross_device(self, dataset, model, role, rank, transport, **kw):
        import jax
        import numpy as np

        from .comm import FedCommManager, create_transport
        from .models import hub

        cfg = self.cfg
        t = cfg.train_args
        backend = transport or cfg.comm_args.extra.get("transport", "loopback")
        tr = create_transport(
            backend, rank,
            run_id=cfg.comm_args.extra.get("run_id", "cd"),
            chaos=cfg.common_args.extra.get("chaos"),
            comm_retry=cfg.common_args.extra.get("comm_retry"),
            **({} if backend == "loopback" else
               {"ip_table": cfg.comm_args.grpc_ipconfig_path or None}))
        comm = FedCommManager(tr, rank)
        if role == "server":
            if model is None or "input_shape" not in kw:
                raise ValueError("cross-device server needs `model` and "
                                 "input_shape=...")
            params = jax.tree.map(np.asarray, hub.init_params(
                model, kw.pop("input_shape"),
                jax.random.key(cfg.common_args.random_seed)))
            from .cross_device import CrossDeviceServer

            return CrossDeviceServer(
                comm, init_params=params, num_rounds=t.comm_round,
                devices_per_round=t.client_num_per_round,
                min_devices=int(t.extra.get("min_devices",
                                            t.client_num_per_round)),
                round_timeout=float(t.extra.get("round_timeout", 30.0)),
                **kw)
        from .cross_device import EdgeClient
        from .cross_silo import SiloTrainer

        if dataset is None or model is None:
            raise ValueError("cross-device client needs `dataset`=(x, y) "
                             "and `model`")
        x, y = dataset
        trainer = SiloTrainer(model.apply, t, x, y, seed=rank)
        return EdgeClient(comm, rank, trainer,
                          uplink_topk=t.extra.get("uplink_topk"), **kw)

    def run(self, *a, **kw):
        return self.runner.run(*a, **kw)
