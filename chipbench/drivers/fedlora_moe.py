"""`fedlora_moe`: the `fedlora` kind for a base too large to stand in
float32 beside its reference, whose expert layers count their own work.

The window is `fedlora`'s, call for call. What differs: the reference keeps
the base in the dtype it was made in (bfloat16: the upcast is exact) and
upcasts a layer at a time itself, where `reference/common.py`'s `run_lora`
casts the whole tree to float32 first (14.8 GB for 3.7 B parameters); the
round's own metrics `moe_pairs` (token-expert pairs computed here) and
`moe_max_rows` (the fullest held expert's rows, a silo's largest over its
layers) are summed over the traced rounds into the log the work functions
read; the faults planted are those this cell can have: half the SILOS
left out (at one sequence a silo half a batch is empty), one held expert
left out of the sum, the chosen weights left unnormalised; and the leaves
the traffic file names under `routing_leaves` (the routers' kernels and
selection biases) are drawn from its `routing_seed`, not from --seed. Which
experts are popular decides how many pairs THIS share computes: drawn from
--seed, six seeds gave 79,000 to 163,000 pairs a round and `train_tok_s`
9,613 to 9,916 (PERF.md section 6, PR 30): the seed was changing the work,
as it did in the serving mix before its schedule was fixed. --seed still
draws the token ids and every other weight."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import inputs
from chipbench.drivers import fedlora
from chipbench.drivers.rounds import FOLLOWED
from chipbench.reference import common

COUNTED = ("moe_pairs", "moe_max_rows")


class Driver(fedlora.Driver):
    CASES = {"control_fp8": {"precision": "fp8"},
             "fault_half_silos": {"half_silos": True},
             "fault_drop_expert": {"fault": "drop_expert"},
             "fault_unnormalised": {"fault": "unnormalised"}}

    # no fault: the reference with its router's input rounded to bfloat16,
    # to read how much of a sound gap is tokens routed otherwise
    PROBES = {"probe_router_bf16": {"fault": "router_bf16"}}

    def controls(self, cases=None) -> dict:
        self.CASES = {**type(self).CASES, **self.PROBES}
        return super().controls(cases or list(type(self).CASES))

    def build(self) -> None:
        super().build()
        self.counted: list = []

    def base(self):
        t = self.traffic
        flat = jax.tree_util.tree_flatten_with_path(self.base_shapes)[0]
        routing = {inputs.path_str(p): s for p, s in flat if any(
            name in inputs.path_str(p) for name in t["routing_leaves"])}
        fixed = inputs.init_tree(routing, t["routing_seed"],
                                 self.config["init_gain"],
                                 self.model["compute_dtype"])
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: fixed.get(inputs.path_str(p), leaf),
            super().base())

    def one_round(self, r: int) -> float:
        out = self.round_fn(self.state, self.cstates, self.data, self.ids,
                            self.w, jax.random.fold_in(self.key, r), None)
        self.state, self.cstates = out.server_state, out.client_states
        got = jax.device_get({k: out.metrics[k]
                              for k in ("train_loss",) + COUNTED})
        # (traced rounds counted before this one, what this one counted):
        # the window counts a traced round only after it has returned
        self.counted.append(
            (self.log["rounds"], {k: float(got[k]) for k in COUNTED}))
        return float(got["train_loss"])

    def work_log(self, rounds: int) -> dict:
        after = [n for n, _ in self.counted[1:]] + [rounds]
        traced = [c for (n, c), m in zip(self.counted, after) if m > n]
        log = {k: sum(c[k] for c in traced) for k in COUNTED}
        last = self.counted[-1][1]
        t = self.traffic
        print(f"[chipbench] experts: {last['moe_pairs']:.0f} pairs computed "
              f"here in the last round of {t['silos'] * t['seq_len']} tokens; "
              f"the fullest held expert took "
              f"{last['moe_max_rows'] / t['silos']:.0f} rows a silo",
              flush=True)
        return {**super().work_log(rounds), **log}

    def reference(self, precision: str = "f32", half_silos: bool = False,
                  fault: str | None = None) -> dict:
        model = {**self.model, "fault": fault} if fault else self.model
        x, y = self.x, self.y
        if half_silos:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        forward = self.cell.reference().forward
        step = jax.jit(lambda base, ad: common.lora_round(
            forward, base, ad, x, y, self.traffic["learning_rate"], model,
            precision))
        base = self.base()
        ad0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                           self.adapters())
        ad, losses, first = ad0, [], None
        for _ in range(FOLLOWED):
            ad, loss = step(base, ad)
            losses.append(float(loss))
            first = ad if first is None else first
        return self.followed({"loss": losses, "params": [first, ad],
                              "params0": ad0})
