"""Cross-silo federation on one box (the reference "octopus" example,
python/examples/federate/quick_start/octopus/ — there run as separate
server/client processes; here composed in-process over loopback. Swap the
transports for "grpc" (+ip table) or "mqtt_s3" (broker) for real
deployments — the managers don't change).

Run:  python examples/cross_silo_federation.py [--secagg]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import uuid

import jax
import numpy as np

from fedml_tpu.comm import FedCommManager, create_transport
from fedml_tpu.comm.loopback import release_router
from fedml_tpu.config import TrainArgs
from fedml_tpu.cross_silo import (
    FedClientManager, FedServerManager, SecAggClientManager,
    SecAggServerManager, SiloTrainer,
)
from fedml_tpu.models import hub

secagg = "--secagg" in sys.argv
run_id = f"example-{uuid.uuid4().hex[:6]}"
n_silos = 3
model = hub.create("lr", 3)
t = TrainArgs(epochs=2, batch_size=16, learning_rate=0.2)
params = jax.tree.map(np.asarray, hub.init_params(model, (8,), jax.random.key(0)))
client_ids = list(range(1, n_silos + 1))

mk = lambda rank: FedCommManager(
    create_transport("loopback", rank, run_id=run_id), rank)

if secagg:
    server = SecAggServerManager(mk(0), client_ids=client_ids,
                                 init_params=params, num_rounds=3)
else:
    # quorum 2-of-3: math.ceil(quorum_frac * 3) must equal 2, so use the
    # exact fraction (0.67 would ceil to 3 and demand every client)
    server = FedServerManager(mk(0), client_ids=client_ids,
                              init_params=params, num_rounds=3,
                              round_timeout=30.0, quorum_frac=2 / 3)

rs = np.random.RandomState(0)
w_true = rs.randn(8, 3)
clients = []
for cid in client_ids:
    x = rs.randn(64, 8).astype(np.float32)
    y = np.argmax(x @ w_true, 1).astype(np.int32)
    trainer = SiloTrainer(model.apply, t, x, y, seed=cid)
    if secagg:
        clients.append(SecAggClientManager(
            mk(cid), cid, trainer, num_clients=n_silos,
            client_ids=client_ids))
    else:
        clients.append(FedClientManager(mk(cid), cid, trainer))

server.run(background=True)
for c in clients:
    c.run(background=True)
for c in clients:
    c.announce_ready()
finished = server.done.wait(timeout=300)
release_router(run_id)
if not finished:
    raise TimeoutError("federation did not finish within 300s "
                       f"(history so far: {server.history})")
print(("secagg " if secagg else "") + "federation history:", server.history)
