"""Federated semantic segmentation (FedSeg): UNet-lite + per-pixel CE with
an ignore label + whole-set mIoU eval (reference:
python/fedml/simulation/mpi/fedseg/FedSegAPI.py — the runtime is the
task-agnostic round engine; the task is the objective + model).

Run:  python examples/federated_segmentation.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.builtin import make_fedavg
from fedml_tpu.config import TrainArgs
from fedml_tpu.core.algorithm import SEG_IGNORE_ID, seg_eval_fn
from fedml_tpu.models import hub
from fedml_tpu.parallel.round import build_round_fn


def square_masks(rs, n_clients, s, hw=16):
    """Synthetic dense-prediction task: segment one bright square."""
    x = 0.1 * rs.randn(n_clients, s, hw, hw, 1).astype(np.float32)
    y = np.zeros((n_clients, s, hw, hw), np.int32)
    for c in range(n_clients):
        for i in range(s):
            h0, w0 = rs.randint(1, hw // 2, 2)
            sz = rs.randint(3, hw // 2)
            x[c, i, h0:h0 + sz, w0:w0 + sz, 0] += 1.0
            y[c, i, h0:h0 + sz, w0:w0 + sz] = 1
    # a sprinkle of ignore pixels (unlabeled regions, reference
    # ignore_index=255 semantics)
    y = np.where(rs.rand(*y.shape) < 0.02, SEG_IGNORE_ID, y)
    return x, y


rs = np.random.RandomState(0)
n_clients, shard = 3, 16
x, y = square_masks(rs, n_clients, shard)
data = {"x": jnp.asarray(x), "y": jnp.asarray(y),
        "mask": jnp.ones((n_clients, shard), jnp.float32)}

model = hub.create("unet", 2)
t = TrainArgs(epochs=1, batch_size=8, learning_rate=0.2,
              extra={"task": "segmentation"})
alg = make_fedavg(model.apply, t)
params = hub.init_params(model, (16, 16, 1), jax.random.key(0))
rnd = build_round_fn(alg, mesh=None)
st = alg.server_init(params, None)
for r in range(6):
    out = rnd(st, jnp.zeros((n_clients,)), data, jnp.arange(n_clients),
              jnp.full((n_clients,), float(shard)),
              jax.random.fold_in(jax.random.key(1), r), None)
    st = out.server_state
    print(f"round {r}: loss={float(out.metrics['train_loss']):.3f} "
          f"pixel_acc={float(out.metrics['train_acc']):.3f}")

# server-side eval: whole-set mIoU via the accumulated confusion matrix
xe, ye = square_masks(np.random.RandomState(7), 1, 8)
ev = seg_eval_fn(model.apply, num_classes=2)
m = ev(st.params, jnp.asarray(xe[0]).reshape(2, 4, 16, 16, 1),
       jnp.asarray(ye[0]).reshape(2, 4, 16, 16),
       jnp.ones((2, 4), jnp.float32))
print(f"eval: miou={float(m['miou']):.3f} acc={float(m['acc']):.3f} "
      f"per_class_iou={np.round(np.asarray(m['per_class_iou']), 3).tolist()}")
assert float(m["miou"]) > 0.6, float(m["miou"])
print("OK federated segmentation")
