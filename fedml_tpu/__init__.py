"""fedml_tpu — a TPU-native federated learning framework.

Brand-new design with the capabilities of the reference FedML
(ray-ruisun/FedML; see SURVEY.md), built JAX/XLA-first: federated rounds are
single jitted SPMD programs over a device mesh (psum = aggregation, replication
= broadcast), not message-passing processes. The message-driven architecture is
kept only where real network boundaries exist (cross-silo; fedml_tpu.comm).

Public API mirrors the reference entry surface (reference:
python/fedml/__init__.py:64 init, launch_simulation.py:9 run_simulation,
data/data_loader.py:234 data.load, model/model_hub.py:19 model.create).
"""
from __future__ import annotations

import logging
import random

import numpy as np

from . import config as _config
from .config import Config, load_config
from .core.registry import ALGORITHMS, DATASETS, MODELS

__version__ = "0.1.0"

__all__ = [
    "Config",
    "load_config",
    "init",
    "run_simulation",
    "FedMLRunner",
    "__version__",
]


def __getattr__(name):
    # lazy: runner pulls in the runtime modules, which import jax
    if name == "FedMLRunner":
        from .runner import FedMLRunner

        return FedMLRunner
    raise AttributeError(name)


def init(config_path: str | None = None, config: Config | dict | None = None,
         **overrides) -> Config:
    """Entry point (reference: fedml.init, python/fedml/__init__.py:64).
    Loads + validates config, seeds host RNGs. Device RNG is handled by
    explicit jax.random keys derived from random_seed — deterministic by
    construction, no global seeding needed on device."""
    if config_path is not None:
        cfg = load_config(config_path)
    elif isinstance(config, Config):
        cfg = config
    elif isinstance(config, dict):
        cfg = Config.from_dict(config)
    else:
        cfg = Config()
    for k, v in overrides.items():
        setattr(cfg, k, v)
    # per-client (data-silo) override files, applied by rank (reference:
    # _update_client_specific_args, python/fedml/__init__.py:188-214)
    from pathlib import Path

    cfg.apply_data_silo_config(
        Path(config_path).expanduser().parent if config_path else None)
    # the ONE deliberate global-seed site (reference parity: fedml.init
    # seeds host RNGs once at entry so user code is reproducible). Library
    # code must never reseed the global numpy RNG mid-run — round-seeded
    # sampling uses local RandomState instances (simulator.sample_clients,
    # parity.py) so chaos/async/data draws sharing np.random stay on the
    # stream this line establishes.
    random.seed(cfg.common_args.random_seed)
    np.random.seed(cfg.common_args.random_seed)
    logging.basicConfig(level=logging.INFO)
    # telemetry sinks (reference: mlops.init wires wandb/MQTT reporting at
    # entry, core/mlops/__init__.py:91; here a local JSONL file + optional
    # wandb, per tracking_args)
    from .utils.sinks import attach_from_config

    attach_from_config(cfg)
    return cfg


def run_simulation(cfg: Config, dataset=None, model=None):
    """reference: fedml.run_simulation (launch_simulation.py:9)."""
    from .simulation.simulator import run_simulation as _run

    return _run(cfg, dataset, model)


def run_async_simulation(cfg: Config, dataset=None, model=None):
    """Staleness-weighted async FL (reference: simulation/mpi/async_fedavg/)."""
    from .simulation.async_simulator import run_async_simulation as _run

    return _run(cfg, dataset, model)
