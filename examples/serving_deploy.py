"""Serving deploy: train federated, publish per-round model artifacts, serve
round N over HTTP (reference: python/fedml/serving/ FedMLInferenceRunner +
the mlops model-artifact upload, core/mlops/__init__.py:388).

Run:  python examples/serving_deploy.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import tempfile
import urllib.request

import numpy as np

import fedml_tpu
from fedml_tpu import mlops
from fedml_tpu.serving import FedMLInferenceRunner, predictor_from_artifact
from fedml_tpu.simulation.simulator import Simulator
from fedml_tpu.utils.artifacts import FileArtifactStore, aggregated_name

cfg = fedml_tpu.init(config={
    "data_args": {"dataset": "digits"},
    "model_args": {"model": "mlp"},
    "train_args": {"federated_optimizer": "FedAvg",
                   "client_num_in_total": 4, "client_num_per_round": 4,
                   "comm_round": 3, "epochs": 1, "batch_size": 32,
                   "learning_rate": 0.1},
    "validation_args": {"frequency_of_the_test": 0},
    "comm_args": {"backend": "sp"},
})
store = FileArtifactStore(os.path.join(tempfile.mkdtemp(), "artifacts"))
mlops.set_artifact_store(store)

sim = Simulator(cfg)
for r in range(3):
    sim.run_round(r)
    mlops.log_aggregated_model_info(r, sim.server_state.params)
print("published:", store.list())
assert aggregated_name(1) in store.list()

# deploy round 1 (not the latest — artifacts are addressable by round)
pred = predictor_from_artifact(store, 1, sim.apply_fn)
runner = FedMLInferenceRunner(pred, host="127.0.0.1", port=0)
runner.start()
try:
    x = np.asarray(sim.dataset.x_test[:4], np.float32)
    req = urllib.request.Request(
        f"http://127.0.0.1:{runner.port}/predict",
        data=json.dumps({"inputs": x.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    out = json.loads(urllib.request.urlopen(req, timeout=10).read())
    print("served predictions:", out["predictions"],
          "labels:", sim.dataset.y_test[:4].tolist())
finally:
    runner.stop()
    mlops.set_artifact_store(None)
print("served round-1 artifact over HTTP")

# --- framework-neutral export (the ONNX/Triton-repo analog): write the
# trained model as manifest.json + tensors.npz, then boot a replica from
# the export alone — the manifest carries the model recipe
from fedml_tpu.serving import export_model
from fedml_tpu.serving.scheduler import start_replica

exp_dir = os.path.join(tempfile.mkdtemp(), "export")
export_model(exp_dir, sim.server_state.params, model_name="mlp",
             num_classes=sim.num_classes, input_shape=(64,))
print("exported:", sorted(os.listdir(exp_dir)))
_rid, runner2 = start_replica({"export_dir": exp_dir, "port": 0})
try:
    req = urllib.request.Request(
        f"http://127.0.0.1:{runner2.port}/predict",
        data=json.dumps({"inputs": x.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    out2 = json.loads(urllib.request.urlopen(req, timeout=10).read())
    print("served from export:", out2["predictions"])
    assert len(out2["predictions"]) == len(x)
finally:
    runner2.stop()
print("OK serving deploy (artifact + export paths)")
