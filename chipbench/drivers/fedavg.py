"""`fedavg`: FedAvg rounds through `fedml_tpu.init` + `Simulator.run_round`."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs
from chipbench.drivers.rounds import FOLLOWED, RoundsDriver


def batch_rows(seed: int, round_idx: int, client_ids, shard: int, batch: int,
               epochs: int):
    """[m, steps, B]: which rows of its shard each client's local steps
    take. The program states the schedule (a fresh permutation per epoch
    from the key fold_in(fold_in(key(seed), round), client id), cut into
    batches); the reference has to be given the same batches, so it is
    written out here with jax.random alone."""
    rkey = jax.random.fold_in(jax.random.key(seed), round_idx)
    nb = shard // batch

    def one(cid):
        keys = jax.random.split(jax.random.fold_in(rkey, cid), epochs)
        perms = jax.vmap(lambda k: jax.random.permutation(k, shard))(keys)
        return perms[:, : nb * batch].reshape(epochs * nb, batch)

    return jax.vmap(one)(jnp.asarray(client_ids))


class Driver(RoundsDriver):
    rate_metric = "round_rate"

    def build(self) -> None:
        import fedml_tpu
        from fedml_tpu.data.fed_dataset import FedDataset
        from fedml_tpu.models.hub import ResNet
        from fedml_tpu.simulation.simulator import Simulator

        m, t = self.model, self.traffic
        cfg = fedml_tpu.init(config={
            "common_args": {"random_seed": self.seed},
            "data_args": {"dataset": "cifar10"},
            "model_args": {"model": "resnet18_gn"},
            "train_args": {
                "federated_optimizer": "FedAvg",
                "client_num_in_total": t["clients_total"],
                "client_num_per_round": t["clients_per_round"],
                "comm_round": 1, "epochs": t["epochs"],
                "batch_size": t["batch_size"],
                "learning_rate": t["learning_rate"],
                "compute_dtype": m["compute_dtype"]},
            "validation_args": {"frequency_of_the_test": 0},
            "comm_args": {"backend": t["backend"]}})
        shape = (m["image_size"], m["image_size"], m["image_channels"])
        self.x, self.y = inputs.gaussian_classes(
            self.seed, t["clients_total"], self.config["samples_per_client"], shape,
            m["num_classes"])
        n, s = t["clients_total"], self.config["samples_per_client"]
        ds = FedDataset(
            x_train=self.x, y_train=self.y,
            mask_train=jnp.ones((n, s), jnp.float32),
            counts=np.full((n,), s, np.int64),
            x_test=np.asarray(self.x[0][:8]), y_test=np.asarray(self.y[0][:8]),
            num_classes=m["num_classes"], synthetic=True)
        model = ResNet(m["num_classes"], tuple(m["stage_sizes"]),
                       m["stem_filters"])
        self.sim = Simulator(cfg, dataset=ds, model=model)
        self.shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.sim.params)
        p0 = self.weights()
        self.sim.params = p0
        self.sim.server_state = self.sim.alg.server_init(p0, cfg)

    def weights(self):
        return inputs.init_tree(self.shapes, self.seed,
                                self.cell.config["init_gain"], jnp.float32)

    def one_round(self, r: int) -> float:
        return float(self.sim.run_round(r)["train_loss"])

    def programs(self):
        """(name, jitted, arguments) of what the window drives, for
        chipbench/compile_check.py."""
        s = self.sim
        ids, w = s._pad_ids(s.sample_clients(0))
        return [("round_fn", s.round_fn, (
            s.server_state, s.client_states, s.data, jnp.asarray(ids),
            jnp.asarray(w), jax.random.key(0), s.hook_state))]

    def trainable(self):
        return self.sim.server_state.params

    def units_per_round(self) -> float:
        return 1.0

    def work_log(self, rounds: int) -> dict:
        t = self.traffic
        return {"samples": rounds * t["clients_per_round"]
                * self.config["samples_per_client"] * t["epochs"]}

    def free(self) -> None:
        self.sim = None

    def reference(self, precision: str = "f32", **fault) -> dict:
        ref = self.cell.reference()
        t = self.traffic
        if t["clients_per_round"] != t["clients_total"]:
            raise ValueError("the fedavg kind follows full participation: "
                             "clients_per_round must equal clients_total")
        ids = np.arange(t["clients_total"], dtype=np.int32)
        rows = [batch_rows(self.seed, r, ids, self.config["samples_per_client"],
                           t["batch_size"], t["epochs"])
                for r in range(FOLLOWED)]
        weights = jnp.full((len(ids),), float(self.config["samples_per_client"]))
        out = ref.run(self.weights(), self.x, self.y, rows, weights,
                      t["learning_rate"], self.model, precision, **fault)
        return self.followed(out)
