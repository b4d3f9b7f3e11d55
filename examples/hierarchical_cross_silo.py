"""Hierarchical cross-silo: intra-silo data parallelism (the reference's
torch-DDP-inside-the-silo, python/fedml/__init__.py:342-390) composed with
cross-silo FedAvg — on TPU both levels are axes of ONE mesh and the whole
round is ONE XLA program (parallel/hier.py).

Run:  python examples/hierarchical_cross_silo.py
      (any device count; 8 virtual CPU devices via
       XLA_FLAGS=--xla_force_host_platform_device_count=8 show a real
       (silos=4, intra=2) layout)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from fedml_tpu.algorithms.builtin import make_fedavg
from fedml_tpu.config import TrainArgs
from fedml_tpu.core.algorithm import make_client_optimizer
from fedml_tpu.models import hub
from fedml_tpu.parallel.hier import make_hier_round, shard_hier_data

devs = jax.devices()
intra = 2 if len(devs) % 2 == 0 and len(devs) > 1 else 1
silos_ax = len(devs) // intra
mesh = Mesh(np.array(devs).reshape(silos_ax, intra), ("silos", "intra"))
print(f"mesh: silos={silos_ax} x intra={intra} on {devs[0].device_kind}")

n_silos = silos_ax * max(1, 4 // silos_ax)   # multiple of the silos axis
shard, batch = 64, 16
model = hub.create("mlp", 3)
t = TrainArgs(epochs=1, batch_size=batch, learning_rate=0.3)
alg = make_fedavg(model.apply, t)
params = hub.init_params(model, (8,), jax.random.key(0))
opt = make_client_optimizer("sgd", t.learning_rate)
rnd = make_hier_round(model.apply, alg, mesh, opt, batch, t.epochs)

rs = np.random.RandomState(0)
w_true = rs.randn(8, 3)
x = rs.randn(n_silos, shard, 8).astype(np.float32)
y = np.argmax(x @ w_true, axis=-1)
data = shard_hier_data(
    {"x": x, "y": y, "mask": np.ones((n_silos, shard), np.float32)}, mesh)

st = alg.server_init(params, None)
ids = jnp.arange(n_silos)
w = jnp.full((n_silos,), float(shard))
for r in range(5):
    st, metrics = rnd(st, data, ids, w, jax.random.fold_in(jax.random.key(1), r))
    print(f"round {r}: loss={float(metrics['train_loss']):.4f} "
          f"acc={float(metrics['train_acc']):.3f}")
assert float(metrics["train_acc"]) > 0.8, "did not learn"
print("hierarchical federation converged")
