"""`fedlora`: federated LoRA rounds through `llm.federated_lora` +
`parallel.round.build_round_fn`, one call a round ending in the fetched
loss (the flat path of examples/fedllm_lora.py and chip_smoke.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import inputs, manifest
from chipbench.drivers.rounds import FOLLOWED, RoundsDriver
from chipbench.reference import common


class Driver(RoundsDriver):
    rate_metric = "train_tok_s"

    def build(self) -> None:
        from fedml_tpu.config import TrainArgs
        from fedml_tpu.llm import federated_lora
        from fedml_tpu.ops.flash_attention import flash_attn_fn
        from fedml_tpu.parallel.round import build_round_fn

        m, t = self.model, self.traffic
        if t["seqs_per_silo"] != t["batch_size"] or t["epochs"] != 1:
            raise ValueError("the fedlora kind follows ONE local step a "
                             "silo: seqs_per_silo == batch_size, epochs 1")
        lm, _spec = manifest.find("models", m["model_type"])(
            m, attn_fn=flash_attn_fn, remat=t["remat"])
        self.base_shapes = inputs.param_shapes(lm)
        base = self.base()
        targs = TrainArgs(epochs=1, batch_size=t["batch_size"],
                          learning_rate=t["learning_rate"],
                          compute_dtype=m["compute_dtype"])
        alg, theirs = federated_lora(lm, base, targs, jax.random.key(0),
                                     rank=t["lora_rank"],
                                     targets=tuple(t["lora_targets"]))
        self.adapter_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), theirs)
        del theirs
        n = t["silos"]
        self.x, self.y = inputs.token_rows(
            self.seed, n, t["seqs_per_silo"], t["seq_len"], m["vocab_size"])
        self.data = {"x": self.x, "y": self.y,
                     "mask": jnp.ones((n, t["seqs_per_silo"]), jnp.float32)}
        self.round_fn = build_round_fn(alg, mesh=None)
        self.state = alg.server_init(self.adapters(), None)
        self.ids = jnp.arange(n)
        self.w = jnp.full((n,), float(t["seqs_per_silo"]))
        self.cstates = jnp.zeros((n,))
        self.key = jax.random.key(self.seed)

    def base(self):
        return inputs.init_tree(self.base_shapes, self.seed,
                                self.config["init_gain"],
                                self.model["compute_dtype"])

    def adapters(self):
        return inputs.init_tree(self.adapter_shapes, self.seed, 1.0,
                                jnp.float32, salt=1)

    def one_round(self, r: int) -> float:
        out = self.round_fn(self.state, self.cstates, self.data, self.ids,
                            self.w, jax.random.fold_in(self.key, r), None)
        self.state, self.cstates = out.server_state, out.client_states
        return float(out.metrics["train_loss"])

    def programs(self):
        return [("round_fn", self.round_fn, (
            self.state, self.cstates, self.data, self.ids, self.w,
            self.key, None))]

    def trainable(self):
        return self.state.params

    def units_per_round(self) -> float:
        t = self.traffic
        return float(t["silos"] * t["seqs_per_silo"] * t["seq_len"])

    def work_log(self, rounds: int) -> dict:
        return {"tokens": rounds * self.units_per_round()}

    def free(self) -> None:
        self.state = self.round_fn = self.data = None

    def reference(self, precision: str = "f32", **fault) -> dict:
        out = common.run_lora(
            self.cell.reference().forward, self.base(), self.adapters(),
            self.x, self.y, FOLLOWED, self.traffic["learning_rate"],
            self.model, precision, **fault)
        return self.followed(out)
