"""`serve_docs`: `serve`'s replica, window, metrics and check (one
`start_replica` replica driven over its own HTTP /predict with `stream: true`
by an open loop; first tokens timed from when they were due), under
DOCUMENTS ASKED SEVERAL TIMES: documents arrive as a Poisson process, each is
asked `asks` times (an opening ask, then follow-ups a lognormal gap after the
previous ask's due time, by the schedule and not by completion), every ask
being the document followed by a fresh question, so a follow-up's prefix hit
is the document and its prefill the question.

What differs from `serve`:

- the schedule (`build_schedule`): generated from `lead_in_s` before the
  window; asks due before 0 are served during set-up, in their order, so that
  their documents are resident and the window opens in steady state; asks due
  at or after the window's end are cut. The mix fixes every length and gap
  (evenly spaced quantiles in the order `schedule_seed` gives); `--seed` draws
  the token ids and the weights;
- the warm-up: one request for every bucket a prompt's last chunk can fall in
  (lengths are whole pages), each behind a full chunk, on the replica's own
  table (`engine_max_len` positions), so the step and every admission program
  the mix reaches are compiled before the lead-in;
- the weights: the routers' kernels and selection biases come from the mix's
  `routing_seed`, every other leaf from `--seed`;
- the reference is computed in blocks of queries and only the compared
  positions' logits are kept;
- two numbers beside `served_logit_gap`, both of the program's selection
  against the reference's at the compared positions of every layer:
  `selection_miss`, 1 less the share of the program's selected positions
  that the reference selects, and `newest_miss`, the same read on the
  positions from the prompt's last page on alone (1 - common / either),
  where an unwritten key shows. The program's side reads the ENGINE's own
  indexer keys of the prompt, out of its `ik` pool through its prefix map
  after the window (`engine_index_keys`): the rows the timed chunks wrote
  and a follow-up's prefix hit found again. The timed path returns tokens
  and no selections, and frees a served token's page with its slot, so the
  queries and the served positions' keys are the program's code
  (`llm.latent.index_inputs`) over the layer inputs the reference hands
  out; the scores and the selection are the program's kernel and code;
- the controls: `control_fp8` and `fault_token_altered` as `serve`'s, and two
  faults this cell can have, planted in the reference: `fault_selection_ignored`
  (attend to every position) and `fault_stale_index` (the indexer's keys from
  the prompt's last page on not written).
"""
from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs, loadgen, manifest
from chipbench.drivers import serve

DRAIN_S = 90.0


def quantiles(spec: dict, n: int, order, multiple: int = 1) -> np.ndarray:
    """n values at the lognormal's evenly spaced quantiles, clipped, rounded
    to whole `multiple`s, in the order `order` gives."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([loadgen._NORMAL.inv_cdf(float(v)) for v in u])
    raw = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                  spec["min"], spec["max"])
    if multiple > 1:
        raw = np.round(raw / multiple) * multiple
    return order.permutation(raw)


def build_schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """[(due, Planned, ask index, question length)] sorted by due time,
    from `-lead_in_s` to `seconds`."""
    lead, asks, page = mix["lead_in_s"], mix["asks"], mix["page"]
    span = lead + seconds
    n = max(1, round(mix["doc_rate"] * span))
    order = np.random.RandomState(mix["schedule_seed"])
    rs = np.random.RandomState(seed % 2 ** 32)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / mix["doc_rate"])
    arrive = np.cumsum(gaps) - gaps[0] * 0.5 - lead
    docs = quantiles(mix["document"], n, order, page).astype(int)
    follow = quantiles(mix["follow_up_s"], n * (asks - 1), order).reshape(
        n, asks - 1)
    questions = quantiles(mix["question"], n * asks, order, page).astype(
        int).reshape(n, asks)
    outputs = np.round(quantiles(mix["output"], n * asks, order)).astype(
        int).reshape(n, asks)
    out = []
    for d in range(n):
        doc = tuple(int(v) for v in rs.randint(1, vocab, int(docs[d])))
        due = float(arrive[d])
        for a in range(asks):
            if a:
                due += float(follow[d, a - 1])
            question = tuple(int(v) for v in rs.randint(
                1, vocab, int(questions[d, a])))
            if due < seconds:
                out.append((due, loadgen.Planned(due, doc + question,
                                                 int(outputs[d, a])),
                            a, len(question)))
    return sorted(out, key=lambda r: r[0])


class Driver(serve.Driver):
    # ------------------------------------------------------------- set-up
    def weights(self):
        """`serve`'s weights from --seed, but for the leaves the traffic file
        names under `routing_leaves` (the routers' kernels and selection
        biases), drawn from its `routing_seed` as `fedlora_moe` draws them:
        which experts take most tokens, and so a chunk's time, is then the
        mix's and not the seed's (PERF.md section 6, PRs 30 and 34)."""
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

    def warm(self) -> None:
        mix, sv = self.traffic, self.traffic["serve"]
        chunk, page = sv["prefill_chunk"], mix["page"]
        lens, b = [], page
        while b <= chunk:
            lens.append(chunk + b)
            b *= 2
        rs = np.random.RandomState(0)
        self.run_now([loadgen.Planned(0.0, tuple(
            int(v) for v in rs.randint(1, self.model["vocab_size"], n)), 4)
            for n in lens], "warm-up")

    def run_now(self, plans: list, what: str) -> None:
        """Serve `plans` in their order, a few at a time, before the
        window."""
        loop = loadgen.OpenLoop("127.0.0.1", self.runner.port, plans,
                                workers=4, timeout_s=600.0)
        loop.start()
        loop.drain(1500.0)
        bad = [r for r in loop.rows if not r.ok]
        if bad:
            raise RuntimeError(f"{what} request failed: status "
                               f"{bad[0].status} {bad[0].error}")

    def setup(self) -> None:
        super().setup()
        self.plan(self.traffic["doc_rate"])

    def plan(self, doc_rate: float) -> None:
        """Make the schedule at `doc_rate` and serve its lead-in: the asks
        due before the window, in their order."""
        self.planned_rate = doc_rate
        self.schedule = build_schedule(
            dict(self.traffic, doc_rate=doc_rate), self.seconds_planned(),
            self.seed, self.model["vocab_size"])
        early = [loadgen.Planned(0.0, p.tokens, p.max_new)
                 for due, p, _a, _q in self.schedule if due < 0]
        print(f"[chipbench] lead-in: {len(early)} asks due before the window "
              f"of {len(self.schedule)} scheduled at {doc_rate} documents/s",
              flush=True)
        self.run_now(early, "lead-in")

    def seconds_planned(self) -> float:
        """The window the schedule is made for: BENCHMARK.json's run_seconds
        (`--seconds` shorter than it cuts the schedule, never re-draws)."""
        return float(self.traffic.get(
            "window_s", manifest.load_manifest()["run_seconds"]))

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict:
        mix = self.traffic
        # chipbench/sweep.py offers another rate by `rate_rps`: documents/s
        if mix.get("rate_rps", self.planned_rate) != self.planned_rate:
            self.plan(mix["rate_rps"])
        due_now = [row for row in self.schedule if 0 <= row[0] < seconds]
        plans = [p for _due, p, _a, _q in due_now]
        loop = loadgen.OpenLoop("127.0.0.1", self.runner.port, plans,
                                timeout_s=300.0)
        engine_done = self.engine_completions()
        loop.start()
        span = None
        if tracer.on:
            # trace from just before the first opening ask due after
            # `trace_after_s`: a prefill, the follow-ups behind it and the
            # steps between its chunks (the schedule is the mix's, so this
            # is the same window whatever --seed)
            after = float(mix.get("trace_after_s", 3.0))
            opening = [due for due, _p, ask, _q in due_now
                       if ask == 0 and due >= after + 1.0]
            after = min(opening[0] - 1.0 if opening else after,
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
        self.asks = [a for _due, _p, a, _q in due_now]
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
        by_ask = lambda first: [t for t, a in zip(ttft, self.asks)
                                if (a == 0) == first] or [end]
        print(f"[chipbench] first token ms: {shape(ttft)}\n"
              f"[chipbench] first token ms, opening asks: "
              f"{shape(by_ask(True))}\n"
              f"[chipbench] first token ms, follow-ups: "
              f"{shape(by_ask(False))}\n"
              f"[chipbench] token gap ms: {shape(gaps or [end])}", flush=True)
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
            self.log = self.docs_log(
                rows, [(a, q) for _due, _p, a, q in due_now], *span)
        metrics = {"ttft_p50_ms": 1e3 * loadgen.percentile(ttft, 50),
                   "ttft_p90_ms": 1e3 * loadgen.percentile(ttft, 90),
                   "gap_p95_ms": 1e3 * loadgen.percentile(gaps or [end], 95)}
        # of `serve`'s three, those whose lists in BENCHMARK.json name this
        # cell (the others stay on the lines above, unheld)
        held = {m["name"] for m in manifest.metrics_for(
            manifest.load_manifest(), self.cell.name, traced=False)}
        return {"attempted": len(rows), "failed": failed,
                "metrics": {k: v for k, v in metrics.items() if k in held}}

    def docs_log(self, rows, asked, a: float, b: float) -> dict:
        """What the traffic asked of the engine inside the traced span, as
        `serve.traced_log` counts it, and beside it what latent attention
        under a selection needs: an opening ask prefills its whole prompt, a
        follow-up its question (the document is whole pages and resident); a
        query at position p sees p + 1 keys, attends min(p + 1, index_topk)
        of them, and needs the indexer's scores only where it sees more."""
        topk = self.model["index_topk"]
        log = dict.fromkeys(
            ("admitted", "emitted_tokens", "context_token_sum",
             "processed_tokens", "prefilled_tokens", "selected_key_sum",
             "scored_key_sum", "decode_selected_key_sum",
             "decode_scored_key_sum"), 0)

        def queries(first: int, n: int, decode: bool) -> None:
            seen = np.arange(first + 1, first + n + 1)
            sel = int(np.minimum(seen, topk).sum())
            scored = int(seen[seen > topk].sum())
            log["selected_key_sum"] += sel
            log["scored_key_sum"] += scored
            if decode:
                log["decode_selected_key_sum"] += sel
                log["decode_scored_key_sum"] += scored

        for r, (ask, question) in zip(rows, asked):
            n = len(r.plan.tokens)
            hit = n - question if ask else 0
            for j, t in enumerate(r.token_times):
                if not a <= t <= b:
                    continue
                if j == 0:
                    log["admitted"] += 1
                    log["prefilled_tokens"] += n - hit
                    queries(hit, n - hit, False)
                else:
                    log["emitted_tokens"] += 1
                    log["context_token_sum"] += n + j
                    queries(n + j - 1, 1, True)
        log["processed_tokens"] = (log["prefilled_tokens"]
                                   + log["emitted_tokens"])
        return log

    # -------------------------------------------------------------- trace
    def programs(self) -> list:
        """The engine's step program and its admission program's text. An
        executable is named for its function, so the admission buckets share
        ONE name (`jit__admit`) and the trace's reader keeps one text a
        name: the full chunk's, which a document's prefill runs (31 of 34
        executions and 93% of the admission time of a traced window, my
        chip run, PR 34, call r2). A question's last-chunk buckets are read
        through it where instruction names coincide and stay bare kinds
        where they do not."""
        eng = self.runner.predictor.engine
        step = super().programs()
        shapes = step[0][2]
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        chunk = jax.ShapeDtypeStruct(
            (1, self.traffic["serve"]["prefill_chunk"]), jnp.int32)
        row = jax.ShapeDtypeStruct((eng._max_pages,), jnp.int32)
        admit = (*shapes, chunk, i32, i32, i32, row,
                 jax.ShapeDtypeStruct((), jnp.float32),
                 jax.ShapeDtypeStruct((), jnp.uint32), i32,
                 jax.ShapeDtypeStruct((), jnp.bool_), i32)
        return step + [("admit", eng._admit_jit, admit)]

    # -------------------------------------------------------------- check
    def reference_rows(self, params, prompt: list, served: list,
                       model: dict, precision: str = "f32", **observers):
        """Reference logits [len(served), V] that chose each served token,
        from one pass over the prompt with its served tokens (padded to whole
        blocks at the end: causal, so the padding changes nothing)."""
        ref = self.cell.reference()
        seq = prompt + served
        pad = ref.BLOCK * (4 if len(seq) > ref.LONG else 1)
        toks = np.zeros((-(-len(seq) // pad) * pad,), np.int32)
        toks[: len(seq)] = seq
        return np.asarray(ref.forward(
            params, jnp.asarray(toks), model, precision,
            rows=(len(prompt) - 1, len(seq) - 1), **observers))

    def engine_index_keys(self, picked: list) -> list:
        """Per sampled request, what the ENGINE holds of its prompt after
        the window: the indexer's keys [L, n * page, Di] of the prompt's
        whole pages, read from its `ik` pool through the prefix map (a
        prompt's whole pages stay resident after retirement; the served
        tokens' own pages are freed with the slot). These are the rows the
        timed programs wrote, a chunk at a time, and a follow-up found
        again by its prefix hit. None where a page is no longer resident."""
        eng = self.runner.predictor.engine
        pool = eng._carry["cache"]["ik"]
        out = []
        for r in picked:
            keys, _hits = eng._prefix_lookup(list(r.plan.tokens))
            held = [eng._prefix.get(k) for k in keys]
            if not held or any(e is None for e in held):
                out.append(None)
                continue
            rows = np.asarray(pool[:, np.asarray([e.page for e in held])])
            out.append(rows.reshape(rows.shape[0], -1, rows.shape[-1]))
        return out

    def program_selection(self, params, lo: int, hi: int, engine_keys):
        """An `observe` hook for the reference: per layer, the PROGRAM's
        selection at query rows lo .. hi - 1 against the reference's own.
        The program's side: its keys of the prompt's whole pages are
        `engine_keys` [L, P, Di], the rows the timed path wrote into the
        engine's pool; its queries, and the keys of the positions after
        them (the served tokens', whose pages the engine has freed), are
        the program's code (`llm.latent.index_inputs`, in the
        configuration's dtype) over the layer input the reference hands
        out; scores and selection are the program's kernel and code
        (`ops.paged_attention.index_scores`, `llm.latent.select_top`).
        Returns (hook, rows), `rows` filling a layer with (1 - the share of
        the program's selection the reference selects too; the same over
        the positions from the prompt's last page on alone, as 1 - common /
        either)."""
        from fedml_tpu.llm import latent as la
        from fedml_tpu.ops.paged_attention import (
            index_scores, latent_block_pages,
        )

        m = self.model
        lat = la.Latent(m["q_lora_rank"], m["kv_lora_rank"],
                        m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"], m["index_n_heads"],
                        m["index_head_dim"], m["index_topk"])
        dtype = jnp.dtype(m["compute_dtype"])
        eps = m["rms_norm_eps"]
        base = float(m["rope_parameters"]["rope_theta"])
        page = self.traffic["serve"]["kv_page_size"]
        newest = (lo // page) * page
        rows: list = []

        @jax.jit
        def missed(bl, h, selected, written):
            t = h.shape[0]
            hb = jnp.asarray(h, dtype)[None]
            pos = jnp.arange(t)[None]
            wq_a = jnp.asarray(bl["wq_a"]["kernel"], dtype)
            norm = jnp.asarray(bl["q_a_norm"]["scale"], dtype)
            # the keys of every position, the queries of the compared rows
            _q, ki, _w = la.index_inputs(
                bl, hb, la.rms_norm(hb @ wq_a, norm, eps), pos, lat, eps, base)
            ki = jnp.concatenate(
                [jnp.asarray(written, ki.dtype), ki[0, written.shape[0]:]])
            mine_rows = hb[:, lo:hi]
            qi, _k, wi = la.index_inputs(
                bl, mine_rows, la.rms_norm(mine_rows @ wq_a, norm, eps),
                pos[:, lo:hi], lat, eps, base)
            n_pages = t // page
            block = latent_block_pages(n_pages)
            scores = index_scores(
                qi, wi, ki.reshape(n_pages, page, -1),
                jnp.arange(n_pages, dtype=jnp.int32)[None],
                jnp.asarray([n_pages], jnp.int32))
            keys = jnp.arange(t).reshape(-1, 1, block * page)
            seen = keys[None] <= jnp.arange(lo, hi)[None, None, :, None]
            mine = la.select_top(scores, seen, lat.index_topk, (1, 3))
            mine = jnp.moveaxis(mine[0], 1, 0).reshape(hi - lo, t)
            return selection_misses(mine, selected, newest, jnp)

        leaves = ("wq_a", "q_a_norm", "index_wq", "index_wk", "index_k_norm",
                  "index_w")

        def hook(i: int, h, selected) -> None:
            bl = params[f"block_{i}"]
            written = (engine_keys[i] if engine_keys is not None
                       else np.zeros((0, lat.index_dim), np.float32))
            rows.append(tuple(float(v) for v in missed(
                {k: bl[k] for k in leaves}, h, selected, written)))

        return hook, rows

    def compare_sample(self, picked: list, engine_keys: list, params) -> list:
        """The float32 reference over every sampled request, once: per
        request {"at": its logits at the served positions, "gaps": how far
        each served token lies under the reference's best, "misses": the
        program's selection against it a layer, "selected": the reference's
        own selection a layer}. Beside the gap it says how near the token
        with the largest gap, and every other, came to routing otherwise
        (`router_edge`): the numbers PERF.md's reading of the gap's tail
        rests on."""
        out = []
        for r, written in zip(picked, engine_keys):
            prompt, served = list(r.plan.tokens), list(r.tokens)
            hook, misses = self.program_selection(
                params, len(prompt) - 1, len(prompt) + len(served) - 1,
                written)
            selected, edges = [], []

            def observe(i, h, sel):
                hook(i, h, sel)
                selected.append(np.asarray(sel))

            t0 = time.perf_counter()
            at = self.reference_rows(
                params, prompt, served, self.model, observe=observe,
                observe_router=lambda i, margin, held: edges.append(
                    np.where(np.asarray(held), np.asarray(margin), np.inf)))
            gaps = at.max(-1) - at[np.arange(len(served)), served]
            out.append({"at": at, "gaps": gaps, "misses": misses,
                        "selected": selected})
            print(f"[chipbench] reference over {len(prompt)} + {len(served)} "
                  f"tokens {time.perf_counter() - t0:.1f} s: gap "
                  f"{float(gaps.max()):.4f}, selection miss "
                  f"{max(a for a, _ in misses):.5f}, on the newest pages "
                  f"{max(b for _, b in misses):.5f}; the prompt's keys from "
                  f"{'the engine pool' if written is not None else 'THE CHECK (pages no longer resident)'}",
                  flush=True)
            if edges:
                # the smallest distance, over the sparse layers, at which a
                # HELD expert sits on the edge of a token's choice
                edge = np.min(edges, axis=0)
                worst = np.argsort(-gaps)[:5]
                near = np.argsort(edge)[:5]
                show = lambda idx: ", ".join(
                    f"{int(j)}: gap {gaps[j]:.4f} edge {edge[j]:.2e}"
                    for j in idx)
                print(f"[chipbench] router edge, served tokens by gap: "
                      f"{show(worst)}\n[chipbench] router edge, served "
                      f"tokens by edge: {show(near)}", flush=True)
        return out

    @staticmethod
    def numbers(rows: list, resident: int) -> dict:
        gaps = np.concatenate([r["gaps"] for r in rows]) if rows \
            else np.zeros((0,))
        misses = [m for r in rows for m in r["misses"]]
        worst = lambda i: max(m[i] for m in misses) if misses \
            else float("nan")
        return {"served_logit_gap": float(gaps.max()) if gaps.size
                else float("nan"),
                "selection_miss": worst(0), "newest_miss": worst(1),
                "_selection_overlap_mean": 1.0 - float(np.mean(
                    [m[0] for m in misses])) if misses else float("nan"),
                "_gap_mean": float(gaps.mean()) if gaps.size
                else float("nan"),
                "_gaps_over_a_tenth": int((gaps > 0.1).sum()),
                "_compared_tokens": int(gaps.size),
                "_compared_requests": len(rows),
                "_prompts_read_from_the_engine_pool": resident}

    def check(self) -> dict:
        picked = self.sample()
        written = self.engine_index_keys(picked)    # before the engine goes
        self.free()
        rows = self.compare_sample(picked, written, self.weights())
        return self.numbers(rows, sum(w is not None for w in written))

    def controls(self, cases=None) -> dict:
        """The cell's own window, whole (so the control's run is one more
        reading of the cell's end-to-end numbers), then, over the same
        sample and ONE float32 pass a request: the program's reading;
        `control_fp8` (the reference in fp8: at each position the gap of
        the token fp8 puts first, and how far its selection misses the
        float32 one); `fault_token_altered`; and the two faults of the
        selection, planted in the reference."""
        from chipbench.trace import Tracer

        self.setup()
        self.window(self.seconds_planned(), Tracer("", 0.0, on=False))
        picked = self.sample()
        written = self.engine_index_keys(picked)
        self.free()
        params = self.weights()
        sound = self.compare_sample(picked, written, params)
        out = {"program": self.numbers(
            sound, sum(w is not None for w in written))}
        vocab = self.model["vocab_size"]
        page = self.traffic["serve"]["kv_page_size"]
        gap_of = lambda at, toks: float(
            (at.max(-1) - at[np.arange(len(toks)), toks]).max())
        rows = {"fault_token_altered": [], "control_fp8": [],
                "fault_selection_ignored": [], "fault_stale_index": []}
        for r, ref in zip(picked, sound):
            prompt, served = list(r.plan.tokens), list(r.tokens)
            lo = len(prompt) - 1
            newest = (lo // page) * page
            flipped = served[:-1] + [(served[-1] + vocab // 2) % vocab]
            rows["fault_token_altered"].append(
                {"served_logit_gap": gap_of(ref["at"], flipped),
                 "selection_miss": 0.0, "newest_miss": 0.0})
            for case, precision, fault in (
                    ("control_fp8", "fp8", None),
                    ("fault_selection_ignored", "f32", "selection_ignored"),
                    ("fault_stale_index", "f32", "stale_index")):
                if cases and case not in cases:
                    continue
                theirs: list = []
                t0 = time.perf_counter()
                low = self.reference_rows(
                    params, prompt, served,
                    dict(self.model, fault=fault, stale_from=newest),
                    precision,
                    observe=lambda i, h, sel: theirs.append(np.asarray(sel)))
                misses = [selection_misses(a, b, newest, np)
                          for a, b in zip(theirs, ref["selected"])]
                rows[case].append(
                    {"served_logit_gap": gap_of(ref["at"], low.argmax(-1)),
                     "selection_miss": max(float(a) for a, _ in misses),
                     "newest_miss": max(float(b) for _, b in misses)})
                print(f"[chipbench] {case} over {len(prompt)} + "
                      f"{len(served)} tokens {time.perf_counter() - t0:.1f} "
                      f"s: {rows[case][-1]}", flush=True)
        # a case reads as a run would judge it, by the worst of its requests
        # (the lines above have each request's, the smallest a limit has to
        # stand under among them)
        for case, got in rows.items():
            if got and (not cases or case in cases):
                out[case] = {k: max(g[k] for g in got) for k in got[0]}
        return {k: v for k, v in out.items() if not cases or k in cases}


def selection_misses(mine, theirs, newest: int, xp):
    """(1 - the share of `mine` [R, T] bool that `theirs` holds too; over
    the key positions from `newest` on alone, 1 - common / either): how far
    two selections of the same queries differ, as a whole and on the pages
    written last, where an unwritten key shows and 16 positions of 2,048
    weigh nothing in the whole (0 where neither selects any of them)."""
    new = xp.arange(mine.shape[1])[None, :] >= newest
    both, either = xp.sum(mine & theirs & new), xp.sum((mine | theirs) & new)
    return (1.0 - xp.sum(mine & theirs) / xp.maximum(1, xp.sum(mine)),
            xp.where(either > 0, 1.0 - both / xp.maximum(1, either), 0.0))
