"""`serve_blocks`: `serve`'s replica and open loop (one `start_replica` replica
driven over its own HTTP /predict with `stream: true` at a fixed rate; first
tokens timed from when they were due) for a model that GENERATES BY DIFFUSION
OVER BLOCKS: a forward yields 0 to `block_length` tokens of a slot, each
streamed with the index within its block of the forward that unmasked it and
its confidence, and every answer has a set length.

What differs from `serve`:

- the schedule (`build_schedule`): `serve`'s arrivals and prompt lengths (the
  distributions' evenly spaced quantiles in the order `schedule_seed` gives),
  every answer exactly `max_new` tokens, prompt ids uniform over the
  vocabulary WITHOUT the mask token; a request names the mix's
  `denoising_steps` and `confidence_threshold` (null: the static rule);
- the weights: the routers' kernels come from the mix's `routing_seed`
  (`routing_leaves`), every other leaf from `--seed`, as `serve_docs` has it;
- the traced log carries, beside `serve`'s request log, the traced span's
  deltas of the engine's counters (block forwards and positions, unmasked
  tokens, the expert layers' pairs and live experts, the windows' context):
  the work functions read shapes and this log alone;
- the check REPLAYS what the timed path produced. For a sampled request the
  state before each forward is rebuilt from the streamed tokens and their
  forward indices, and ONE reference pass per forward index over `[clean
  sequence ; noised copy of the generated part]` under the mask "clean:
  block-causal; noised block b: the clean blocks before b, and itself both
  ways" gives every block's logits at that index. The numbers:
  `served_logit_gap` (the reference's best logit minus its logit of the
  served token, at the forward that unmasked it) and `confidence_gap` (the
  largest difference between the log of a streamed confidence and the
  reference's log-probability of that token in that state: probabilities over
  152 thousand tokens are of the order of 1e-4, so the difference is taken
  of their logarithms), with `confidence_drift`, the MEAN of that difference
  over the compared tokens (one position routed otherwise hardly moves it,
  a lower precision moves it everywhere). A request's last block, where it
  is cut by the budget, is left out: its undelivered positions' tokens are
  not known;
- the controls: `control_fp8` and `fault_token_altered` as `serve`'s, and two
  faults a block-diffusion engine can have, planted in the reference's mask:
  `fault_block_causal` (a block attends causally inside itself) and
  `fault_uncommitted` (a noised block sees NOISED earlier blocks: an engine
  that skipped the commit). A control or fault reads as the tokens and
  confidences IT would have served, held against the sound reference.
"""
from __future__ import annotations

import http.client
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs, loadgen, manifest
from chipbench.drivers import serve

PAD_TO = 256            # a replayed sequence is padded to a few lengths
# the engine's counters whose deltas over the traced span the log carries
COUNTED = ("block_forwards", "commit_forwards", "block_positions",
           "unmasked_tokens", "moe_pairs", "moe_experts_live",
           "block_context", "context_keys", "steps", "slot_steps")


def build_schedule(mix: dict, seconds: float, seed: int, vocab: int,
                   mask_id: int) -> list:
    """`loadgen.build_schedule`'s arrivals and prompt lengths; every answer
    `max_new` tokens; ids in [1, vocab) without `mask_id`."""
    n = max(1, round(mix["rate_rps"] * seconds))
    order = np.random.RandomState(mix["schedule_seed"])
    rs = np.random.RandomState(seed % 2 ** 32)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / mix["rate_rps"])
    prompts = order.permutation(loadgen._lengths(mix["prompt"], n))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    out = []
    for t, p in zip(due, prompts):
        if t >= seconds:
            break
        ids = rs.randint(1, vocab - 1, int(p))
        out.append(loadgen.Planned(
            float(t), tuple(int(v) + int(v >= mask_id) for v in ids),
            int(mix["max_new"])))
    return out


class OpenLoop(loadgen.OpenLoop):
    """`loadgen.OpenLoop` whose requests name the denoising parameters and
    whose rows keep, beside every token, the forward that unmasked it and
    its confidence (`row.notes`)."""

    def __init__(self, host, port, schedule, denoising: dict, **kw):
        super().__init__(host, port, schedule, **kw)
        self.denoising = denoising
        for row in self.rows:
            row.notes = []

    def _issue(self, row) -> None:
        row.sent = self.now()
        body = json.dumps({"tokens": list(row.plan.tokens),
                           "max_new_tokens": row.plan.max_new,
                           "stream": True, **self.denoising})
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            conn.request("POST", "/predict", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            row.status = resp.status
            if resp.status != 200:
                row.error = resp.read(300).decode("utf-8", "replace")
                return
            for raw in resp:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                ev = json.loads(line[5:])
                if "token" in ev:
                    row.token_times.append(self.now())
                    row.tokens.append(int(ev["token"]))
                    row.notes.append((int(ev["forward"]),
                                      float(ev["confidence"])))
                elif ev.get("done"):
                    row.done = list(ev["generated_tokens"]) == row.tokens
                    if not row.done:
                        row.error = "done frame differs from the stream"
                    break
                elif "error" in ev:
                    row.status = int(ev.get("code", 503))
                    row.error = str(ev["error"])[:300]
                    break
        except (OSError, http.client.HTTPException, ValueError,
                KeyError) as e:
            row.status = row.status if row.status not in (0, 200) else 599
            row.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            conn.close()


def engine_counters() -> dict:
    from fedml_tpu.utils import metrics as mx

    held = mx.snapshot()["counters"]
    return {k: held.get(f"serving.engine.{k}", 0) for k in COUNTED} | {
        "prompt_tokens": held.get("serving.prompt_tokens", 0)}


class Driver(serve.Driver):
    # ------------------------------------------------------------- set-up
    def weights(self):
        """`serve`'s weights from --seed, but for the leaves the traffic
        file names under `routing_leaves` (the routers' kernels), drawn from
        its `routing_seed`: which experts take most rows is then the mix's
        and not the seed's (PERF.md section 6, PRs 30 and 34)."""
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

    def denoising(self) -> dict:
        return {"denoising_steps": self.traffic["denoising_steps"],
                "confidence_threshold": self.traffic["confidence_threshold"]}

    def warm(self) -> None:
        """Every program the mix can reach: the block program, the full
        prefill chunk and each bucket of a prompt's last chunk (whole
        blocks: 4, 8, ... up to the chunk)."""
        mix, chunk = self.traffic, self.traffic["serve"]["prefill_chunk"]
        block = self.model["block_length"]
        lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
        lens, b = [], block
        while b <= chunk:
            n = b if b >= lo else chunk + b
            if lo <= n <= hi:
                lens.append(n)
            b *= 2
        lens.append(hi)
        rs = np.random.RandomState(0)
        plans = [loadgen.Planned(0.0, tuple(
            int(v) for v in rs.randint(1, self.model["mask_token_id"], n)),
            2 * block) for n in lens]
        loop = OpenLoop("127.0.0.1", self.runner.port, plans,
                        self.denoising(), timeout_s=600.0)
        loop.start()
        loop.drain(900.0)
        bad = [r for r in loop.rows if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up request failed: status "
                               f"{bad[0].status} {bad[0].error}")

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict:
        mix = self.traffic
        plans = build_schedule(mix, seconds, self.seed,
                               self.model["vocab_size"],
                               self.model["mask_token_id"])
        loop = OpenLoop("127.0.0.1", self.runner.port, plans,
                        self.denoising())
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
            a, before = loop.now(), engine_counters()
            time.sleep(tracer.seconds)
            span, after_c = (a, loop.now()), engine_counters()
            tracer.stop()
        time.sleep(max(0.0, seconds - loop.now()))
        loop.drain(seconds + serve.DRAIN_S)
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
            ttft.append((r.token_times[0] if r.ok else end) - r.plan.due)
            gaps += [b - a for a, b in zip(r.token_times, r.token_times[1:])]
        shape = lambda v: " ".join(
            f"p{q} {1e3 * loadgen.percentile(v, q):.2f}"
            for q in (50, 75, 90, 95, 99)) + \
            f" mean {1e3 * statistics.fmean(v):.2f} over {len(v)}"
        # a forward's tokens arrive together: the gaps between bursts apart
        stalls = [g for g in gaps if g > 1e-3] or [end]
        print(f"[chipbench] first token ms: {shape(ttft)}\n"
              f"[chipbench] token gap ms: {shape(gaps or [end])}\n"
              f"[chipbench] gap between bursts ms: {shape(stalls)}; "
              f"{len(gaps) - len(stalls)} gaps inside a burst",
              flush=True)
        failed = sum(not r.ok for r in rows)
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
            self.log = {**self.traced_log(rows, *span),
                        **{k: after_c[k] - before[k] for k in after_c}}
        metrics = {"ttft_p50_ms": 1e3 * loadgen.percentile(ttft, 50),
                   "ttft_p90_ms": 1e3 * loadgen.percentile(ttft, 90),
                   "gap_p95_ms": 1e3 * loadgen.percentile(gaps or [end], 95)}
        # of `serve`'s three, those whose lists in BENCHMARK.json name this
        # cell (the others stay on the lines above, unheld)
        held = {m["name"] for m in manifest.metrics_for(
            manifest.load_manifest(), self.cell.name, traced=False)}
        return {"attempted": len(rows), "failed": failed,
                "metrics": {k: v for k, v in metrics.items() if k in held}}

    # -------------------------------------------------------------- trace
    def programs(self) -> list:
        """The engine's block program and its admission program's text (the
        full chunk's: the buckets share the executable's name)."""
        eng = self.runner.predictor.engine
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (eng.params, eng.adapters, eng._carry))
        sds = jax.ShapeDtypeStruct
        i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
        admit = (*shapes,
                 sds((1, self.traffic["serve"]["prefill_chunk"]), jnp.int32),
                 i32, i32, i32, sds((eng._max_pages,), jnp.int32), f32,
                 sds((), jnp.uint32), i32, sds((), jnp.bool_), i32,
                 sds((self.model["block_length"],), jnp.int32), i32, f32)
        return [("block", eng._block_jit, shapes),
                ("admit", eng._admit_jit, admit)]

    # -------------------------------------------------------------- check
    def replay_inputs(self, r, f: int, fault=None):
        """(ids [T], mask [T, T], positions [T], rows, where) of the pass
        that replays forward index `f` of request `r`: the clean sequence
        (the prompt and every final token of a whole block), then the noised
        copy of the generated part as it stood BEFORE that forward; `rows`
        index the noised positions that forward unmasked and `where` their
        place among the served tokens."""
        block, mask_id = self.model["block_length"], \
            self.model["mask_token_id"]
        prompt, served = list(r.plan.tokens), list(r.tokens)
        plen = len(prompt)
        start = plen // block * block
        end = (plen + len(served)) // block * block
        seq = np.array((prompt + served)[:end], np.int32)
        at = np.array([n[0] for n in r.notes])
        n_noised = end - start
        pos = np.concatenate([np.arange(end), np.arange(start, end)])
        # a noised position holds its final token once an earlier forward
        # unmasked it (the prompt's tail always), the mask token before
        final = np.ones(n_noised, bool)
        gen = np.arange(max(plen, start), end)
        final[gen - start] = at[gen - plen] < f
        ids = np.concatenate([seq, np.where(final, seq[start:], mask_id)])
        t = -(-len(ids) // PAD_TO) * PAD_TO
        blk = pos // block
        mask = np.zeros((t, t), bool)
        real = slice(0, len(ids))
        sees = np.zeros((len(ids), len(ids)), bool)
        # clean rows: block-causal over the clean sequence
        sees[:end, :end] = blk[:end, None] >= blk[None, :end]
        # a noised block: the clean blocks before it, and itself both ways
        sees[end:, :end] = blk[end:, None] > blk[None, :end]
        sees[end:, end:] = blk[end:, None] == blk[None, end:]
        if fault == "block_causal":
            sees[end:, end:] &= pos[end:, None] >= pos[None, end:]
        elif fault == "uncommitted":
            # earlier blocks as their last denoising forward left them
            sees[end:, :end] &= (pos[None, :end] < start)
            sees[end:, end:] = blk[end:, None] >= blk[None, end:]
        mask[real, real] = sees
        mask[np.arange(len(ids), t), np.arange(len(ids), t)] = True
        hit = gen[at[gen - plen] == f]
        pad = lambda a: np.concatenate(
            [a, np.zeros(t - len(a), a.dtype)]).astype(np.int32)
        return (pad(ids), mask, pad(pos), end + (hit - start), hit - plen)

    def replay(self, params, r, precision: str = "f32", fault=None) -> dict:
        """The reference's logits [n, V] of every compared token of request
        `r` in the state it was unmasked in (n served tokens of whole
        blocks, in order of position), from one pass a forward index."""
        ref = self.cell.reference()
        n = (len(r.plan.tokens) + len(r.tokens)) \
            // self.model["block_length"] * self.model["block_length"] \
            - len(r.plan.tokens)
        out = np.zeros((n, self.model["vocab_size"]), np.float32)
        for f in sorted({note[0] for note in r.notes[:n]}):
            ids, mask, pos, rows, where = self.replay_inputs(r, f, fault)
            keep = where < n
            out[where[keep]] = np.asarray(ref.forward(
                params, jnp.asarray(ids), self.model, precision,
                mask=jnp.asarray(mask), positions=jnp.asarray(pos),
                rows=rows[keep]))
        return {"at": out, "served": np.array(r.tokens[:n]),
                "conf": np.array([c for _f, c in r.notes[:n]])}

    @staticmethod
    def read(at: np.ndarray, tokens, conf) -> dict:
        """The two numbers of `tokens` served with confidences `conf`,
        against reference logits `at` [n, V]."""
        at = at.astype(np.float64)
        top = at.max(-1)
        lse = top + np.log(np.exp(at - top[:, None]).sum(-1))
        mine = at[np.arange(len(tokens)), tokens]
        gaps = top - mine
        off = np.abs(np.log(np.maximum(conf, 1e-300)) - (mine - lse))
        return {"served_logit_gap": float(gaps.max()),
                "confidence_gap": float(off.max()),
                "confidence_drift": float(off.mean()),
                "_gap_mean": float(gaps.mean()),
                "_gap_p99": float(np.percentile(gaps, 99)),
                "_confidence_p99": float(np.percentile(off, 99))}

    @staticmethod
    def as_served(at: np.ndarray):
        """(tokens, confidences) a program with logits `at` serves."""
        at = at.astype(np.float64)
        best = at.argmax(-1)
        top = at.max(-1)
        return best, 1.0 / np.exp(at - top[:, None]).sum(-1)

    @staticmethod
    def worst(rows: list) -> dict:
        return {k: max(r[k] for r in rows) for k in rows[0]} if rows else {
            k: float("nan") for k in ("served_logit_gap", "confidence_gap",
                                      "confidence_drift")}

    def sample(self) -> list:
        """`serve`'s sample (the longest request and more, `compare_tokens`
        served tokens), of the requests whose every token carries its
        forward and confidence."""
        self.rows = [r for r in self.rows
                     if len(getattr(r, "notes", ())) == len(r.tokens)]
        return super().sample()

    def check(self) -> dict:
        self.free()
        picked = self.sample()
        params = self.weights()
        rows, tokens = [], 0
        for r in picked:
            t0 = time.perf_counter()
            got = self.replay(params, r)
            rows.append(self.read(got["at"], got["served"], got["conf"]))
            tokens += len(got["served"])
            print(f"[chipbench] replay of {len(r.plan.tokens)} + "
                  f"{len(r.tokens)} tokens {time.perf_counter() - t0:.1f} s:"
                  f" {rows[-1]}", flush=True)
        return {**self.worst(rows), "_compared_tokens": tokens,
                "_compared_requests": len(picked)}

    def controls(self, cases=None) -> dict:
        """A short window at the cell's own load, then, over the same
        sample: the program's reading; `fault_token_altered` (the last
        compared token of a request altered); and `control_fp8`,
        `fault_block_causal`, `fault_uncommitted`: what a program with the
        reference's fp8 arithmetic, or with that mask, would have served,
        held against the sound reference."""
        from chipbench.trace import Tracer

        self.setup()
        self.window(float(self.traffic.get("control_seconds", 8.0)),
                    Tracer("", 0.0, on=False))
        self.free()
        picked = self.sample()
        params = self.weights()
        vocab = self.model["vocab_size"]
        rows: dict = {}
        add = lambda case, row: rows.setdefault(case, []).append(row)
        for r in picked:
            sound = self.replay(params, r)
            at, served = sound["at"], sound["served"]
            add("program", self.read(at, served, sound["conf"]))
            print(f"[chipbench] program over {len(r.plan.tokens)} + "
                  f"{len(r.tokens)} tokens: {rows['program'][-1]}",
                  flush=True)
            flipped = served.copy()
            flipped[-1] = (flipped[-1] + vocab // 2) % vocab
            add("fault_token_altered", self.read(at, flipped, sound["conf"]))
            for case, precision, fault in (
                    ("control_fp8", "fp8", None),
                    ("fault_block_causal", "f32", "block_causal"),
                    ("fault_uncommitted", "f32", "uncommitted")):
                if cases and case not in cases:
                    continue
                t0 = time.perf_counter()
                theirs = self.replay(params, r, precision, fault)["at"]
                add(case, self.read(at, *self.as_served(theirs)))
                print(f"[chipbench] {case} over {len(r.plan.tokens)} + "
                      f"{len(r.tokens)} tokens "
                      f"{time.perf_counter() - t0:.1f} s: {rows[case][-1]}",
                      flush=True)
        return {case: self.worst(got) for case, got in rows.items()
                if not cases or case in cases}
