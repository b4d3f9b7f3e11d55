"""`serve`: one replica from `serving.scheduler.start_replica`, driven over
its own HTTP /predict with `stream: true` by an open loop at a fixed rate."""
from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs, loadgen, manifest
from chipbench.drivers import Base
from chipbench.reference import common

DRAIN_S = 60.0          # a minute past the close, then a request failed
PAD_TO = 256            # reference sequences are padded to a few lengths

# the five spans a request's first token is made of, under `serving.request`
FIVE = ("serving.http.in", "serving.engine.queue", "serving.engine.prefill",
        "serving.engine.first_fetch", "serving.http.out")


class Driver(Base):
    # a request's states, not what a thread was doing
    states = ("serving.request",) + FIVE

    def __init__(self, cell, seed: int, rehearse: bool):
        self.cell, self.seed = cell, seed
        self.traffic, self.config, self.model = cell.sizes(rehearse)
        self.log: dict = {}
        self.rows: list = []
        self.runner = None

    # ------------------------------------------------------------- set-up
    def weights(self):
        return inputs.init_tree(self.shapes, self.seed,
                                self.config["init_gain"],
                                self.model["compute_dtype"])

    def setup(self) -> None:
        from fedml_tpu.serving.scheduler import start_replica

        lm, spec = manifest.find("models", self.model["model_type"])(
            self.model)
        self.shapes = inputs.param_shapes(lm)
        _job, self.runner = start_replica({
            **spec, "params": self.weights(), "port": 0,
            "serve": dict(self.traffic["serve"])})
        self.warm()

    def warm(self) -> None:
        """Every program the mix can reach: the step, the full prefill
        chunk and each power-of-two bucket of a prompt's last chunk."""
        mix, chunk = self.traffic, self.traffic["serve"]["prefill_chunk"]
        lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
        lens, b = [], 1
        while b <= chunk:
            n = b if b >= lo else chunk + b
            if lo <= n <= hi:
                lens.append(n)
            b *= 2
        lens.append(hi)
        rs = np.random.RandomState(0)
        plans = [loadgen.Planned(0.0, tuple(
            int(v) for v in rs.randint(1, self.model["vocab_size"], n)), 4)
            for n in lens]
        loop = loadgen.OpenLoop("127.0.0.1", self.runner.port, plans)
        loop.start()
        loop.drain(600.0)
        bad = [r for r in loop.rows if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up request failed: status "
                               f"{bad[0].status} {bad[0].error}")

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict:
        mix = self.traffic
        plans = loadgen.build_schedule(mix, seconds, self.seed,
                                       self.model["vocab_size"])
        loop = loadgen.OpenLoop("127.0.0.1", self.runner.port, plans)
        engine_done = self.engine_completions()
        loop.start()
        span = None
        if tracer.on:
            after = min(float(mix.get("trace_after_s", 3.0)),
                        max(0.0, seconds - tracer.seconds))
            time.sleep(max(0.0, after - loop.now()))
            tracer.start()
            time.sleep(0.5)         # let the first steps after the start go by
            tracer.open()
            a = loop.now()
            time.sleep(tracer.seconds)
            span = (a, loop.now())
            tracer.stop()
        time.sleep(max(0.0, seconds - loop.now()))
        loop.drain(seconds + DRAIN_S)
        end = loop.now()
        self.rows = rows = loop.rows
        late = [r.sent - r.plan.due for r in rows if r.sent == r.sent]
        print(f"[chipbench] generator lateness ms: p50 "
              f"{1e3 * loadgen.percentile(late, 50):.2f} p95 "
              f"{1e3 * loadgen.percentile(late, 95):.2f} max "
              f"{1e3 * max(late):.2f} over {len(late)} sent of "
              f"{len(rows)} due", flush=True)
        ttft, gaps = [], []
        for r in rows:
            # a failed request misses every tail: it waited to the end
            ttft.append((r.token_times[0] if r.ok else end) - r.plan.due)
            gaps += [b - a for a, b in zip(r.token_times, r.token_times[1:])]
        # the whole shape beside the three statistics that are reported
        shape = lambda v: " ".join(
            f"p{q} {1e3 * loadgen.percentile(v, q):.2f}"
            for q in (50, 75, 90, 95, 99)) + \
            f" mean {1e3 * statistics.fmean(v):.2f} over {len(v)}"
        print(f"[chipbench] first token ms: {shape(ttft)}\n"
              f"[chipbench] token gap ms: {shape(gaps or [end])}", flush=True)
        failed = sum(not r.ok for r in rows)
        # a 200 the engine did not complete came from the predictor's
        # per-request fallback: not the path this cell times
        by_engine = self.engine_completions() - engine_done
        if by_engine < len(rows) - failed:
            print(f"[chipbench] the engine completed {by_engine} of "
                  f"{len(rows) - failed} answered requests", flush=True)
            failed = len(rows) - by_engine
        for r in [r for r in rows if not r.ok][:5]:
            print(f"[chipbench] failed request due {r.plan.due:.3f}: status "
                  f"{r.status} done {r.done} tokens {len(r.tokens)}/"
                  f"{r.plan.max_new} {r.error}", flush=True)
        if span:
            self.log = self.traced_log(rows, *span)
        return {"attempted": len(rows), "failed": failed,
                "metrics": {
                    "ttft_p50_ms": 1e3 * loadgen.percentile(ttft, 50),
                    "ttft_p90_ms": 1e3 * loadgen.percentile(ttft, 90),
                    "gap_p95_ms": 1e3 * loadgen.percentile(gaps or [end], 95)}}

    @staticmethod
    def engine_completions() -> int:
        from fedml_tpu.utils import metrics as mx

        return int(mx.snapshot()["counters"].get(
            "serving.engine.completions", 0))

    @staticmethod
    def traced_log(rows, a: float, b: float) -> dict:
        """What the traffic asked of the engine inside the traced span
        [a, b] (host clock): token 0 of a request comes from its prefill,
        token j >= 1 from a decode step over a context of prompt + j."""
        admitted = emitted = ctx = prompt_tokens = 0
        for r in rows:
            n = len(r.plan.tokens)
            for j, t in enumerate(r.token_times):
                if not a <= t <= b:
                    continue
                if j == 0:
                    admitted += 1
                    prompt_tokens += n
                else:
                    emitted += 1
                    ctx += n + j
        return {"admitted": admitted, "emitted_tokens": emitted,
                "context_token_sum": ctx,
                "processed_tokens": prompt_tokens + emitted}

    # -------------------------------------------------------------- trace
    def programs(self) -> list:
        """The engine's step program (the admit buckets' text is not read)."""
        eng = self.runner.predictor.engine
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (eng.params, eng.adapters, eng._carry))
        return [("step", eng._step_jit, shapes)]

    # -------------------------------------------------------------- check
    def free(self) -> None:
        if self.runner is not None:
            self.runner.stop()
        self.runner = None
        gc.collect()

    def sample(self) -> list:
        """Finished requests drawn from the seed, the longest among them,
        until some hundreds of served tokens are held."""
        done = [r for r in self.rows if r.ok]
        if not done:
            return []
        want = int(self.traffic.get("compare_tokens", 300))
        longest = max(done, key=lambda r: len(r.plan.tokens) + len(r.tokens))
        rs = np.random.RandomState(self.seed % 2 ** 32)
        picked, n = [longest], len(longest.tokens)
        for i in rs.permutation(len(done)):
            if n >= want or len(picked) >= 12:
                break
            if done[i] is not longest:
                picked.append(done[i])
                n += len(done[i].tokens)
        return picked

    @staticmethod
    def logits_at_served(logits_fn, prompt: list, served: list) -> np.ndarray:
        """[len(served), V]: the logits that chose each served token, from
        one pass over the prompt with its served tokens (padded at the end
        to one of a few lengths; causal, so the padding changes nothing)."""
        seq = prompt + served
        toks = np.zeros((-(-len(seq) // PAD_TO) * PAD_TO,), np.int32)
        toks[: len(seq)] = seq
        return np.asarray(logits_fn(jnp.asarray(toks)))[
            len(prompt) - 1: len(seq) - 1]

    def served_gaps(self, picked, logits_fn) -> np.ndarray:
        """For every served token of the sample: how far its reference
        logit lies below the reference's best at that position."""
        out = []
        for r in picked:
            served = list(r.tokens)
            at = self.logits_at_served(logits_fn, list(r.plan.tokens), served)
            out.append(at.max(-1) - at[np.arange(len(served)), served])
        return np.concatenate(out) if out else np.zeros((0,))

    def reference_logits(self, precision: str = "f32"):
        return common.sequence_logits(self.cell.reference().forward,
                                      self.weights(), self.model, precision)

    def check(self) -> dict:
        self.free()
        picked = self.sample()
        gaps = self.served_gaps(picked, self.reference_logits())
        return {"served_logit_gap": float(gaps.max()) if gaps.size
                else float("nan"),
                "_compared_tokens": int(gaps.size),
                "_compared_requests": len(picked)}

    def controls(self, cases=None) -> dict:
        """A short window at the cell's own load, then, over the same
        sample: the program's reading; the control (the reference in fp8:
        at each position the gap of the token fp8 puts first); and the
        fault "a token altered where it is produced"."""
        from chipbench.trace import Tracer

        self.setup()
        self.window(float(self.traffic.get("control_seconds", 8.0)),
                    Tracer("", 0.0, on=False))
        self.free()
        picked = self.sample()
        pairs = [(list(r.plan.tokens), list(r.tokens)) for r in picked]
        f32 = self.reference_logits()
        hi = [self.logits_at_served(f32, *p) for p in pairs]
        vocab = self.model["vocab_size"]
        flip = lambda s: s[:-1] + [(s[-1] + vocab // 2) % vocab]
        gap_of = lambda at, toks: float(
            (at.max(-1) - at[np.arange(len(toks)), toks]).max())
        out = {"program": {"served_logit_gap": max(
                   gap_of(at, p[1]) for at, p in zip(hi, pairs))},
               "fault_token_altered": {"served_logit_gap": max(
                   gap_of(at, flip(p[1])) for at, p in zip(hi, pairs))}}
        del f32                     # one float32 copy of the weights at a time
        gc.collect()
        low = self.reference_logits("fp8")
        out["control_fp8"] = {"served_logit_gap": max(
            gap_of(at, self.logits_at_served(low, *p).argmax(-1))
            for at, p in zip(hi, pairs))}
        return {k: v for k, v in out.items() if not cases or k in cases}
