"""Centralized (non-federated) baseline trainer.

(reference: python/fedml/centralized/centralized_trainer.py — 164 LoC torch
loop over the pooled dataset; exists so federated results can be compared
against ordinary training on the same data/model/optimizer.)

TPU design: pool the stacked client shards, then one jitted lax.scan epoch
(core/algorithm.local_sgd is exactly that loop) — the baseline uses the
same hot path the federated engine uses, so perf/accuracy comparisons
isolate the FEDERATION, not implementation differences.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..core.algorithm import (
    eval_step_fn, local_sgd, make_batch_indices, make_client_optimizer,
    make_objective,
)
from ..data.fed_dataset import FedDataset
from ..models import hub as model_hub
from ..utils.events import recorder

Pytree = Any


def pool_clients(dataset: FedDataset) -> dict:
    """Concatenate the stacked [N, S, ...] client shards into one pooled
    shard, dropping padding rows via the mask."""
    x = np.asarray(dataset.x_train).reshape(
        (-1,) + dataset.x_train.shape[2:])
    y = np.asarray(dataset.y_train).reshape(-1)
    m = np.asarray(dataset.mask_train).reshape(-1)
    keep = m > 0
    return {"x": x[keep], "y": y[keep],
            "mask": np.ones(int(keep.sum()), np.float32)}


class CentralizedTrainer:
    """Plain SGD on pooled data (reference: centralized_trainer.py)."""

    def __init__(self, cfg: Config, dataset: Optional[FedDataset] = None,
                 model=None):
        from ..data import loader as data_loader
        from ..utils import enable_compilation_cache

        self.cfg = cfg
        t = cfg.train_args
        enable_compilation_cache()   # before the first trace
        # opt-in live /metrics endpoint (common_args.extra.metrics_port)
        from ..utils.prometheus import maybe_start_metrics_server

        self.metrics_exporter = maybe_start_metrics_server(cfg)
        self.dataset = dataset if dataset is not None else data_loader.load(cfg)
        self.model = model if model is not None else model_hub.create(
            cfg.model_args.model, self.dataset.num_classes,
            **cfg.model_args.extra)
        self.apply_fn = model_hub.mixed_precision_apply(
            self.model.apply, t.compute_dtype)
        self.params = model_hub.init_params(
            self.model, self.dataset.x_train.shape[2:],
            jax.random.key(cfg.common_args.random_seed))
        # model-parallel params via the ONE partition-rule registry
        # (parallel/partition.py): a device_args.mesh_shape naming an `mp`
        # axis shards the params with the model's rule table
        # (device_args.partition_rules overrides the auto pick;
        # device_args.unmatched_params opts into replicating params the
        # table misses — the default is a hard error). The jitted epoch
        # inherits the layout from the param inputs; optimizer state
        # follows automatically (opt.init's zeros_like preserves
        # shardings).
        self.mesh = None
        self.param_specs = None
        mesh_shape = cfg.device_args.mesh_shape
        if mesh_shape and "mp" in mesh_shape:
            from ..parallel import partition
            from ..parallel.mesh import make_mesh

            self.mesh = make_mesh(mesh_shape)
            table = (cfg.device_args.extra.get("partition_rules")
                     or partition.table_for_model(self.model))
            self.param_specs = partition.resolve(
                table, self.params, axis="mp",
                on_unmatched=cfg.device_args.extra.get(
                    "unmatched_params", partition.ERROR))
            self.params = partition.shard_params(
                self.params, self.mesh, specs=self.param_specs)
        self.pooled = {k: jnp.asarray(v)
                       for k, v in pool_clients(self.dataset).items()}
        self.opt = make_client_optimizer(
            t.client_optimizer, t.learning_rate, t.momentum, t.weight_decay)
        # optimizer state persists ACROSS epochs (momentum/Adam moments
        # must not reset at epoch boundaries — this is ordinary training)
        self.opt_state = self.opt.init(self.params)
        self.objective = make_objective(t.extra.get("task"))
        self._train = jax.jit(self._epoch)
        from ..core.algorithm import make_eval_fn

        self._eval = make_eval_fn(self.apply_fn, t.extra.get("task"),
                                  self.dataset.num_classes)
        self.history: list[dict] = []

    def _epoch(self, params, opt_state, rng):
        t = self.cfg.train_args
        idx = make_batch_indices(
            rng, self.pooled["y"].shape[0], t.batch_size, 1)
        params, metrics, _steps, opt_state = local_sgd(
            self.apply_fn, params, self.pooled, idx, self.opt,
            objective=self.objective, opt_state=opt_state,
            return_opt_state=True)
        if self.mesh is not None:
            # pin the epoch's OUTPUT params to the registry layout: the
            # compiler is otherwise free to pick its own output shardings,
            # and the layout would drift from the resolved spec table
            # after the first epoch (observed: a bias re-sharded to
            # P('mp') on CPU) — breaking the "one table, one layout"
            # contract checkpoints rely on
            from jax.sharding import NamedSharding

            params = jax.tree.map(
                lambda p, s: jax.lax.with_sharding_constraint(
                    p, NamedSharding(self.mesh, s)),
                params, self.param_specs)
        return params, opt_state, (metrics.loss_sum, metrics.correct,
                                   metrics.count)

    def evaluate(self) -> dict:
        from ..simulation.simulator import _pad_test_batches

        t = self.cfg.train_args
        xb, yb, mb = _pad_test_batches(
            self.dataset.x_test, self.dataset.y_test, max(t.batch_size, 64))
        m = jax.device_get(self._eval(
            self.params, jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb)))
        out = {"test_loss": float(m["loss"]), "test_acc": float(m["acc"])}
        if "miou" in m:                    # segmentation task head
            out["test_miou"] = float(m["miou"])
        return out

    def run(self, epochs: Optional[int] = None) -> list[dict]:
        t = self.cfg.train_args
        n_epochs = epochs if epochs is not None else t.epochs
        from ..utils import metrics as _mx

        for e in range(n_epochs):
            rng = jax.random.fold_in(
                jax.random.key(self.cfg.common_args.random_seed), e)
            _mx.set_gauge("fed.epoch", float(e))
            with recorder.span("centralized_epoch", epoch=e):
                self.params, self.opt_state, (lsum, correct, cnt) = \
                    self._train(self.params, self.opt_state, rng)
            n = max(float(cnt), 1.0)
            row = {"epoch": e, "train_loss": float(lsum) / n,
                   "train_acc": float(correct) / n}
            if e == n_epochs - 1:
                row.update(self.evaluate())
            self.history.append(row)
            recorder.log(row)
        return self.history
