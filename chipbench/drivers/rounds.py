"""What the two training kinds share: a window of rounds, one call a round.

Set-up builds ONE object (the program's compiled round with its state),
drives it from the seed through its first three rounds by the window's own
call, keeps each round's loss and the norms of the state's change after one
round and after three, and hands that same object to the window. `check`
then frees it and lets the plain reference follow the same three rounds.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp

from chipbench import compare
from chipbench.drivers import Base

FOLLOWED = 3        # rounds the reference follows
MIN_TRACED = 3      # whole rounds a traced window holds at the least


class RoundsDriver(Base):
    rate_metric = ""            # the end-to-end metric this kind reports

    def __init__(self, cell, seed: int, rehearse: bool):
        self.cell, self.seed = cell, seed
        self.traffic, self.config, self.model = cell.sizes(rehearse)
        self.log: dict = {"rounds": 0}
        self.got: dict = {}
        self.next_round = 0

    # -- what a kind provides
    def build(self) -> None: raise NotImplementedError
    def one_round(self, r: int) -> float: raise NotImplementedError
    def trainable(self): raise NotImplementedError
    def units_per_round(self) -> float: raise NotImplementedError
    def free(self) -> None: raise NotImplementedError
    def reference(self, precision: str = "f32", **fault) -> dict:
        raise NotImplementedError

    # -- the run
    def setup(self) -> None:
        self.build()
        p0 = jax.tree.map(jnp.array, self.trainable())  # the rounds donate
        losses = []
        for r in range(FOLLOWED):
            losses.append(self.one_round(r))
            if r == 0:
                grad1 = compare.leaf_norms(
                    compare.tree_sub(self.trainable(), p0))
        change = compare.leaf_norms(compare.tree_sub(self.trainable(), p0))
        self.got = {"loss": losses, "grad1": grad1, "change": change}
        self.next_round = FOLLOWED

    def window(self, seconds: float, tracer) -> dict:
        attempted = failed = 0
        took = []
        t0 = time.perf_counter()
        last = t0
        tracer.start()
        settle = tracer.on          # one round between start_trace and the span
        while True:
            r = self.next_round
            self.next_round += 1
            attempted += 1
            try:
                with tracer.span("round"):
                    loss = self.one_round(r)
                ok = math.isfinite(loss)
            except Exception as e:  # noqa: BLE001 — a failed round is counted
                print(f"[chipbench] round {r} raised: {e!r}", flush=True)
                ok = False
            failed += not ok
            took.append(time.perf_counter() - last)
            last = time.perf_counter()
            if tracer.active:
                self.log["rounds"] += 1
                if tracer.due() and self.log["rounds"] >= MIN_TRACED:
                    tracer.stop()
            elif settle:
                settle = False
                tracer.open()
            if tracer.done or (last - t0 >= seconds and not tracer.active):
                break
        tracer.stop()
        # a stalled round shows here, not only as a slower rate
        print(f"[chipbench] seconds a round: median "
              f"{sorted(took)[len(took) // 2]:.4f}, slowest {max(took):.4f} "
              f"(round {took.index(max(took))} of {len(took)})", flush=True)
        done = attempted - failed
        self.log.update(self.work_log(self.log["rounds"]))
        return {"attempted": attempted, "failed": failed,
                "metrics": {self.rate_metric:
                            done * self.units_per_round() / (last - t0)}}

    def work_log(self, rounds: int) -> dict:
        return {}

    @staticmethod
    def followed(out: dict) -> dict:
        """What is compared of a reference run (`params0`, `params` after
        the first and the last round, `loss` of each): the same three
        things set-up keeps of the program's."""
        p0, (first, last) = out["params0"], out["params"]
        return {"loss": out["loss"],
                "grad1": compare.leaf_norms(compare.tree_sub(first, p0)),
                "change": compare.leaf_norms(compare.tree_sub(last, p0))}

    CASES = {"control_fp8": {"precision": "fp8"},
             "fault_half_batch": {"half_batch": True}}

    def controls(self, cases=None) -> dict:
        """The reference in the program's place: in lower precision (fp8,
        the control for a configuration that states bfloat16) and with each
        fault planted. A state left unchanged reads 1 on every gap by
        construction and needs no run."""
        self.build()
        self.free()
        gc.collect()
        ref = self.reference()
        return {name: compare.training_numbers(
                    self.reference(**self.CASES[name]), ref)
                for name in (cases or self.CASES)}

    def check(self) -> dict:
        self.free()
        gc.collect()
        ref = self.reference()
        return compare.training_numbers(self.got, ref)
