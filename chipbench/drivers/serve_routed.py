"""`serve_routed`: `serve`'s replica, open loop and comparison (one
`start_replica` replica driven over its own HTTP /predict with `stream: true`
by an open loop at a fixed rate, first tokens timed from when they were due,
served tokens held against the reference's logits) for a model whose layers
route tokens to experts and whose window layers keep a ring of pages a slot.

What differs from `serve`:

- the weights: the routers' kernels come from the mix's `routing_seed`
  (`routing_leaves`), every other leaf from `--seed`, as `serve_blocks` and
  `serve_docs` have it, so that which experts take most rows is the mix's
  and not the seed's;
- the traced log carries, beside `serve`'s request log, the traced span's
  deltas of the engine's counters (the keys the window layers' queries
  attend and would attend with no window, steps, live slots) and
  `window_step_keys`: for every token the window's decode steps emitted, the
  keys a window layer's query attended there (its context, at most the
  window), which is what the window kernel walks;
- the check keeps the base in the dtype it was made in and runs the
  reference's jitted stages over it (a float32 copy of 3.97 B parameters
  would not fit), over each compared request padded to a multiple of
  `pad_to`, and reads the head only at the served positions;
- the end-to-end metrics are those of `serve`'s three that BENCHMARK.json
  lists for the cell;
- the controls: `control_fp8` and `fault_token_altered` as `serve`'s, and
  the three faults the configuration names, each the reference run with it
  planted (`reference.FAULTS`: window layers attending every key, global
  layers rotated, the router reading the feed-forward's input), read as the
  tokens IT would have served, held against the sound reference.

The numbers: `served_logit_gap` (the worst compared token's reference best
logit minus its logit of the served token) and `served_logit_drift` (the
mean of that gap over the compared tokens: one position routed otherwise
hardly moves it, a lower precision moves it everywhere).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs, manifest
from chipbench.drivers import serve

# the engine's counters whose deltas over the traced span the log carries
COUNTED = ("window_keys", "window_context_keys", "context_keys", "steps",
           "slot_steps", "prefill_chunks")


def engine_counters() -> dict:
    from fedml_tpu.utils import metrics as mx

    held = mx.snapshot()["counters"]
    return {k: held.get(f"serving.engine.{k}", 0) for k in COUNTED} | {
        "prompt_tokens": held.get("serving.prompt_tokens", 0)}


class _Counting:
    """The window's tracer, noting the engine's counters as the traced span
    opens and as it closes."""

    def __init__(self, tracer):
        self.tracer, self.before, self.after = tracer, None, None

    def __getattr__(self, name):
        return getattr(self.tracer, name)

    def open(self):
        self.tracer.open()
        self.before = engine_counters()

    def stop(self):
        self.after = engine_counters()
        self.tracer.stop()


class Driver(serve.Driver):
    # ------------------------------------------------------------- set-up
    def weights(self):
        """`serve`'s weights from --seed, but for the leaves the traffic
        file names under `routing_leaves` (the routers' kernels), drawn from
        its `routing_seed`."""
        t = self.traffic
        flat = jax.tree_util.tree_flatten_with_path(self.shapes)[0]
        routing = {inputs.path_str(p): s for p, s in flat if any(
            name in inputs.path_str(p) for name in t["routing_leaves"])}
        fixed = inputs.init_tree(routing, t["routing_seed"],
                                 self.config["init_gain"],
                                 self.model["compute_dtype"])
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: fixed.get(inputs.path_str(p), leaf),
            super().weights())

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict:
        counting = _Counting(tracer)
        res = super().window(seconds, counting)
        if counting.after is not None:
            self.log.update({k: counting.after[k] - counting.before[k]
                             for k in counting.after})
        held = {m["name"] for m in manifest.metrics_for(
            manifest.load_manifest(), self.cell.name, traced=False)}
        return {**res, "metrics": {k: v for k, v in res["metrics"].items()
                                   if k in held}}

    def traced_log(self, rows, a: float, b: float) -> dict:
        """`serve`'s log of the traced span [a, b], and `window_step_keys`:
        for every token a decode step emitted in it, the keys a window
        layer's query attended (its context, at most the window)."""
        window = self.model["sliding_window_size"]
        keys = 0
        for r in rows:
            n = len(r.plan.tokens)
            keys += sum(min(n + j, window) for j, t in
                        enumerate(r.token_times) if j and a <= t <= b)
        return {**serve.Driver.traced_log(rows, a, b),
                "window_step_keys": keys}

    # -------------------------------------------------------------- trace
    def programs(self) -> list:
        """The engine's step program and its admission program's text (the
        full chunk's: the buckets share the executable's name)."""
        eng = self.runner.predictor.engine
        step = super().programs()
        sds = jax.ShapeDtypeStruct
        i32 = sds((), jnp.int32)
        admit = (*step[0][2],
                 sds((1, self.traffic["serve"]["prefill_chunk"]), jnp.int32),
                 i32, i32, i32, sds((eng._max_pages,), jnp.int32),
                 sds((), jnp.float32), sds((), jnp.uint32), i32,
                 sds((), jnp.bool_), i32)
        return step + [("admit", eng._admit_jit, admit)]

    # -------------------------------------------------------------- check
    def reference_logits(self, precision: str = "f32", fault=None,
                         params=None):
        """tokens [T], rows -> the reference's logits [len(rows), V] over
        the base as it was made (`params`, made anew when None: one copy of
        the base fits beside the reference, two do not), with `fault`
        planted."""
        ref = self.cell.reference()
        params = self.weights() if params is None else params
        return lambda tokens, rows: ref.forward(
            params, tokens, self.model, precision, rows=rows, fault=fault)

    def logits_at_served(self, logits_fn, prompt: list,
                         served: list) -> np.ndarray:
        """[len(served), V]: the logits that chose each served token, from
        one pass over the prompt with its served tokens, padded at the end
        to a multiple of `pad_to` (causal: the padding changes nothing)."""
        seq = prompt + served
        pad = int(self.traffic["pad_to"])
        toks = np.zeros((-(-len(seq) // pad) * pad,), np.int32)
        toks[: len(seq)] = seq
        return np.asarray(logits_fn(jnp.asarray(toks), np.arange(
            len(prompt) - 1, len(seq) - 1)), np.float64)

    @staticmethod
    def read(at: np.ndarray, tokens) -> dict:
        gaps = at.max(-1) - at[np.arange(len(tokens)), tokens]
        return {"served_logit_gap": float(gaps.max()),
                "served_logit_drift": float(gaps.mean()), "_n": len(gaps)}

    @staticmethod
    def worst(rows: list) -> dict:
        """Over requests: the largest gap, and the drift of all tokens."""
        n = sum(r["_n"] for r in rows)
        return {"served_logit_gap": max(r["served_logit_gap"] for r in rows),
                "served_logit_drift": sum(r["served_logit_drift"] * r["_n"]
                                          for r in rows) / n,
                "_compared_tokens": n}

    def check(self) -> dict:
        self.free()
        picked = self.sample()
        if not picked:
            return {"served_logit_gap": float("nan"),
                    "served_logit_drift": float("nan")}
        f32 = self.reference_logits()
        rows = []
        for r in picked:
            t0 = time.perf_counter()
            at = self.logits_at_served(f32, list(r.plan.tokens),
                                       list(r.tokens))
            rows.append(self.read(at, list(r.tokens)))
            print(f"[chipbench] reference over {len(r.plan.tokens)} + "
                  f"{len(r.tokens)} tokens {time.perf_counter() - t0:.1f} s:"
                  f" {rows[-1]}", flush=True)
        return {**self.worst(rows), "_compared_requests": len(picked)}

    def controls(self, cases=None) -> dict:
        """A short window at the cell's own load, then, over the same
        sample: the program's reading; `fault_token_altered` (a request's
        last served token altered); and `control_fp8` and the configuration's
        three faults: what a program with the reference's fp8 arithmetic,
        or with that fault, would have served, held against the sound
        reference."""
        from chipbench.trace import Tracer

        self.setup()
        self.window(float(self.traffic.get("control_seconds", 8.0)),
                    Tracer("", 0.0, on=False))
        self.free()
        picked = self.sample()
        pairs = [(list(r.plan.tokens), list(r.tokens)) for r in picked]
        params = self.weights()
        f32 = self.reference_logits(params=params)
        sound = [self.logits_at_served(f32, *p) for p in pairs]
        vocab = self.model["vocab_size"]
        out = {"program": [self.read(at, p[1])
                           for at, p in zip(sound, pairs)],
               "fault_token_altered": [
                   self.read(at, p[1][:-1] + [(p[1][-1] + vocab // 2)
                                              % vocab])
                   for at, p in zip(sound, pairs)]}
        ref = self.cell.reference()
        for case, precision, fault in (
                ("control_fp8", "fp8", None),
                *((f"fault_{f}", "f32", f) for f in ref.FAULTS)):
            if cases and case not in cases:
                continue
            t0 = time.perf_counter()
            theirs = self.reference_logits(precision, fault, params)
            out[case] = [self.read(at, self.logits_at_served(
                theirs, *p).argmax(-1)) for at, p in zip(sound, pairs)]
            print(f"[chipbench] {case} {time.perf_counter() - t0:.1f} s: "
                  f"{self.worst(out[case])}", flush=True)
        return {case: self.worst(got) for case, got in out.items()
                if not cases or case in cases}
