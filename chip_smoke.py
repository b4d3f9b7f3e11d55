#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # on a machine with a TPU; exit 0 = proof
    python chip_smoke.py --dry-run-cpu  # debug THIS SCRIPT in a CPU sandbox

ONE process, no children (a chip belongs to one process). It drives the three
main paths once, through the entry points a user calls, at the full width of
the 1.2B LLaMA-shaped model the repo times (depth is not cut either; the
weights are random, made from a seed), and checks what comes out:

  round   fedml_tpu.init + Simulator on the flagship exactly as
          bench._flagship_config states it (ResNet-18-GN, CIFAR-10-shaped
          synthetic data, 100 clients x 96 samples, batch 32, bf16): three
          per-round dispatches + evaluate(), then one rounds_per_block=3
          block on a second Simulator. On a multi-chip host the `xla`
          backend shards the clients over every chip.
  fedllm  llm.federated_lora + parallel.round.build_round_fn (the flat path
          of examples/fedllm_lora.py) on TransformerLM(vocab 32000, d2048,
          L16, H16, ff8192, scan_layers, flash attention, remat), LoRA r8,
          bf16, 8 silos x batch 4 x T=2048, two rounds — the flash
          custom_vjp kernels under the round engine's client vmap/scan.
  serve   serving.scheduler.start_replica with an "lm" spec of the same
          shape: eight concurrent POST /predict requests (prompts 64-1024
          tokens, 32 new tokens, two streamed) against a gather-path
          replica (the oracle), then the Pallas paged-kernel replica, the
          kernel replica with int8 KV pages, and a spec_decode=ngram
          replica (the C>1 verify program); on a multi-chip host also a
          tensor-parallel (engine_mp = chips) kernel replica.

What "right" means here (PERF.md "Bring-up" has the measurements behind the
two tolerances):
- every fetched value is finite and of the expected shape; in both training
  phases the loss after the last round is not above the first round's, on
  seeded data with something to learn;
- the Pallas kernels are checked where a check can be exact: the paged
  kernel against plain gather-then-attend math on the same pool, on the
  chip, bf16 and int8, C=1 and C=spec_k+1, to a tolerance set by the dtype;
- replicas are compared by TEACHER-FORCED greedy agreement with the gather
  replica: positions up to and including each stream's first divergence
  (there both sides saw the same context). With random bf16 weights two
  accumulation orders flip a near-tied argmax about once in twenty tokens
  (measured on the v5e: 0.95 for the bf16 kernel, 0.93 with int8 pages),
  so token identity — and a 0.99 bar — is a rounding lottery (the f32
  identity pins stay in tier-1); a wrong decode kernel diverges at every
  stream's second token and scores at most 1/2. AGREEMENT_FLOOR sits
  halfway between the two;
- for every phase with a Pallas call, the kernels are read off the lowered
  PROGRAM (utils/xla_ledger `kernels`: the tpu_custom_call kernel names) —
  an interpreted kernel leaves none — and each engine is checked to have
  served its requests itself (the predictor's per-request fallback would
  answer 200 too).

Stdout: one `[chip_smoke] <phase>: ...` line per phase, one
`[chip_smoke] report {...}` line with everything (jax version, phases, wall
and compile seconds, cache entries, native_available, dry_run), and LAST the
verdict and nothing else:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
Exit 0 only when every phase passed; a failed phase: exit 1. No TPU (or a
device kind the peak table does not know): exit 2 within seconds, nothing on
stdout.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

AGREEMENT_FLOOR = 0.75
# paged kernel vs gather math on the same inputs, as max |diff| / max |ref|:
# bf16 carries 8 significand bits (eps 2^-8); the two sides round p and the
# p.V partial sums at different points, so allow a few eps. f32 (the dry
# run) runs the kernel at HIGHEST precision.
KERNEL_TOL = {"bfloat16": 8 * 2.0 ** -8, "float32": 1e-4}

LM_FULL = dict(vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
               d_ff=8192)
SIZES = {
    False: dict(
        flagship={}, shard=None,       # bench's own numbers
        lm=LM_FULL, dtype="bfloat16",
        fedllm=dict(silos=8, seqs=4, t=2048, batch=4, lr=0.1, rank=8),
        serve=dict(slots=8, max_len=2048, page=16, chunk=256, new=32,
                   spec_k=4,
                   prompt_lens=(64, 320, 256, 512, 576, 768, 832, 1024)),
    ),
    True: dict(                        # --dry-run-cpu: tiny, interpreted
        flagship={"model_args": {"model": "lr"},
                  "train_args": {"client_num_in_total": 8,
                                 "client_num_per_round": 8,
                                 "batch_size": 8}},
        shard=16,
        lm=dict(vocab_size=128, d_model=64, n_layers=2, n_heads=2, d_ff=128),
        dtype="float32",
        fedllm=dict(silos=2, seqs=2, t=32, batch=2, lr=0.5, rank=4),
        serve=dict(slots=4, max_len=64, page=4, chunk=8, new=8, spec_k=2,
                   prompt_lens=(8, 20, 16, 12)),
    ),
}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (cache loads
    included), summed from jax.monitoring's compile events."""

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.total += float(duration)


def _finite(x) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(x, np.float64))))


def _counters() -> dict:
    from fedml_tpu.utils import metrics as mx

    return dict(mx.snapshot()["counters"])


# ------------------------------------------------------------------ round
def phase_round(sz: dict, n_dev: int, dry: bool) -> dict:
    import jax

    import bench
    import fedml_tpu
    from fedml_tpu.simulation.simulator import Simulator
    from fedml_tpu.utils import metrics as mx
    from fedml_tpu.utils import xla_ledger

    backend = "xla" if n_dev > 1 else "sp"     # bench.bench_tpu's rule

    def config(extra=None):
        c = bench._flagship_config(backend)
        for sect, over in sz["flagship"].items():
            c[sect].update(over)
        cfg = fedml_tpu.init(config=c)
        cfg.data_args.extra["synthetic_samples_per_client"] = (
            sz["shard"] or bench.SHARD)
        cfg.train_args.extra.update(extra or {})
        return cfg

    sim = Simulator(config())
    rows = [sim.run_round(0), sim.run_round(1)]
    compiles = mx.snapshot()["gauges"].get("xla.compiles.round_fn")
    check(compiles == 1, f"xla.compiles.round_fn == {compiles} after the "
          "warm round (expected 1: one program, no retrace)")
    rows.append(sim.run_round(2))
    ev = sim.evaluate()
    losses = [r["train_loss"] for r in rows]
    check(all(_finite(list(r.values())) for r in rows), f"round rows: {rows}")
    check(_finite(list(ev.values())), f"eval row: {ev}")
    check(losses[-1] <= losses[0], f"flagship train loss rose: {losses}")
    prog = xla_ledger.programs().get("round_fn")
    check(prog and prog.get("flops", 0) > 0,
          f"XLA ledger did not capture round_fn (a TPU lowering's "
          f"cost_analysis): {prog}")
    out = {"backend": backend, "train_loss": [round(x, 4) for x in losses],
           "test_acc": round(ev["test_acc"], 4),
           "round_fn_gflops": round(prog["flops"] / 1e9, 1)}
    if n_dev > 1:
        # every chip holds a shard of the client data ...
        held = {s.device.id for s in sim.data["x"].addressable_shards}
        check(len(held) == n_dev, f"client data on devices {sorted(held)} "
              f"of {n_dev}")
    if n_dev > 1 and not dry:       # ... and live buffers (CPUs keep no stats)
        in_use = {d.id: d.memory_stats()["bytes_in_use"]
                  for d in jax.devices()}
        check(all(v > 0 for v in in_use.values()),
              f"a chip holds nothing: bytes_in_use {in_use}")
        out["bytes_in_use"] = in_use
    del sim
    gc.collect()

    # round-block execution (K rounds scanned inside one program)
    sim_b = Simulator(config({"rounds_per_block": 3}))
    hist = sim_b.run(3)
    check(sim_b.block_fn is not None and sim_b.block_fn._cache_size() == 1,
          "the blocked run did not go through ONE block program")
    blosses = [h["train_loss"] for h in hist]
    check(len(hist) == 3 and _finite(blosses), f"block history: {hist}")
    check(blosses[-1] <= blosses[0], f"blocked train loss rose: {blosses}")
    # same seeds, same round body: the block replays the three rounds
    check(all(abs(a - b) <= 0.02 * abs(a) + 1e-6
              for a, b in zip(losses, blosses)),
          f"blocked rounds {blosses} disagree with per-round {losses}")
    out["block_train_loss"] = [round(x, 4) for x in blosses]
    return out


# ----------------------------------------------------------------- fedllm
def _lm_params(model, dtype):
    """Seeded random params of the LM in `dtype`, made on the device."""
    import jax
    import jax.numpy as jnp

    def init(r):
        p = model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree.map(lambda a: a.astype(dtype), p)

    return jax.jit(init)(jax.random.key(0))


def phase_fedllm(sz: dict, dry: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.config import TrainArgs
    from fedml_tpu.llm import TransformerLM, count_params, federated_lora
    from fedml_tpu.ops.flash_attention import flash_attn_fn
    from fedml_tpu.parallel.round import build_round_fn
    from fedml_tpu.utils import xla_ledger

    f = sz["fedllm"]
    xla_ledger.reset()          # round_fn below is THIS phase's program
    model = TransformerLM(**sz["lm"], scan_layers=True, attn_fn=flash_attn_fn,
                          remat=True)
    base = _lm_params(model, sz["dtype"])
    n_params = count_params(base)
    t = TrainArgs(epochs=1, batch_size=f["batch"], learning_rate=f["lr"],
                  compute_dtype=sz["dtype"])
    alg, adapters = federated_lora(model, base, t, jax.random.key(1),
                                   rank=f["rank"])
    # structured sequences as in examples/fedllm_lora.py: each token's
    # successor is the next id of a 64-cycle — something to learn
    rs = np.random.RandomState(0)
    cyc = min(64, sz["lm"]["vocab_size"])
    seqs = (rs.randint(0, cyc, (f["silos"], f["seqs"], 1))
            + np.arange(f["t"] + 1)) % cyc
    data = {"x": jnp.asarray(seqs[:, :, :-1], jnp.int32),
            "y": jnp.asarray(seqs[:, :, 1:], jnp.int32),
            "mask": jnp.ones((f["silos"], f["seqs"]), jnp.float32)}
    rnd = build_round_fn(alg, mesh=None)
    st = alg.server_init(adapters, None)
    ids = jnp.arange(f["silos"])
    w = jnp.full((f["silos"],), float(f["seqs"]))
    losses = []
    for r in range(2):
        out = rnd(st, jnp.zeros((f["silos"],)), data, ids, w,
                  jax.random.fold_in(jax.random.key(2), r), None)
        st = out.server_state
        losses.append(float(out.metrics["train_loss"]))
    check(_finite(losses), f"fedllm losses: {losses}")
    check(losses[-1] <= losses[0], f"fedllm train loss rose: {losses}")
    moved = float(sum(jnp.abs(v["b"]).sum() for v in st.params.values()))
    check(_finite(moved) and moved > 0, "the adapters never moved")
    kernels = (xla_ledger.programs().get("round_fn") or {}).get("kernels")
    if not dry:
        check(kernels is not None
              and {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
              <= set(kernels),
              f"flash forward/backward are not Mosaic kernels of the round "
              f"program: {kernels}")
    return {"params": n_params, "train_loss": [round(x, 4) for x in losses],
            "tokens_per_round": f["silos"] * f["seqs"] * f["t"],
            "mosaic_kernels": kernels}


# ------------------------------------------------------------------ serve
def _kernel_vs_gather(sz: dict) -> dict:
    """ops.paged_attention against gather-then-attend math on the same
    pool — the deterministic half of the serve check."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.paged_attention import paged_attention

    sv, lm = sz["serve"], sz["lm"]
    s_, ps, h = sv["slots"], sv["page"], lm["n_heads"]
    dh = lm["d_model"] // h
    mp = -(-sv["max_len"] // ps)
    n_pages = s_ * mp + 1
    dtype = jnp.dtype(sz["dtype"])
    k0 = jax.random.key(3)
    kf, vf = (jax.random.normal(jax.random.fold_in(k0, i),
                                (n_pages, ps, h, dh), jnp.float32)
              for i in (0, 1))
    # every slot owns its own pages (page 0 stays the null page) and sits
    # at a different depth, the last one at the very end of its table
    pages = 1 + jnp.arange(s_ * mp, dtype=jnp.int32).reshape(s_, mp)

    def quantize(x):
        s = jnp.max(jnp.abs(x), axis=(1, 3)) / 127.0           # [P, H]
        q = jnp.clip(jnp.round(x / s[:, None, :, None]), -127, 127)
        return q.astype(jnp.int8), s

    def ref(q, kk, vv, pos):
        """kk/vv [S, T, H, Dh] f32 gathered views."""
        c = q.shape[1]
        hi = jax.lax.Precision.HIGHEST
        sc = jnp.einsum("sqhd,skhd->shqk", q.astype(jnp.float32), kk,
                        precision=hi) * dh ** -0.5
        live = (jnp.arange(kk.shape[1])[None, None, :]
                <= (pos[:, None] + jnp.arange(c))[:, :, None])
        sc = jnp.where(live[:, None], sc, -1e30)
        return jnp.einsum("shqk,skhd->sqhd", jax.nn.softmax(sc, -1), vv,
                          precision=hi)

    out = {}
    for c in (1, sv["spec_k"] + 1):
        q = jax.random.normal(jax.random.fold_in(k0, 10 + c),
                              (s_, c, h, dh), jnp.float32).astype(dtype)
        pos = jnp.linspace(ps // 2, mp * ps - c, s_).astype(jnp.int32)
        for quant in (False, True):
            if quant:
                (kq, ks), (vq, vs) = quantize(kf), quantize(vf)
                got = paged_attention(q, kq, vq, pages, pos, ks, vs)
                deq = lambda x, s: (x.astype(jnp.float32)
                                    * s[:, None, :, None]).astype(dtype)
                kd, vd = deq(kq, ks), deq(vq, vs)
            else:
                kd, vd = kf.astype(dtype), vf.astype(dtype)
                got = paged_attention(q, kd, vd, pages, pos)
            gather = lambda x: x[pages].reshape(
                s_, mp * ps, h, dh).astype(jnp.float32)
            want = ref(q, gather(kd), gather(vd), pos)
            got = got.astype(jnp.float32)
            check(got.shape == want.shape and _finite(got),
                  f"paged kernel output {got.shape} / non-finite")
            err = float(jnp.max(jnp.abs(got - want))
                        / jnp.max(jnp.abs(want)))
            tol = KERNEL_TOL[sz["dtype"]]
            name = f"c{c}_{'int8' if quant else sz['dtype']}"
            check(err <= tol, f"paged kernel vs gather math ({name}): "
                  f"relative error {err:.2e} > {tol:.2e}")
            out[name] = float(f"{err:.3g}")
    return out


def _post(url: str, body: dict, timeout: float = 600.0) -> list:
    """POST /predict; returns the generated tokens. A streamed request's
    SSE token events must spell exactly its final `done` frame."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        check(r.status == 200, f"/predict answered {r.status}")
        if not body.get("stream"):
            return json.loads(r.read())["generated_tokens"]
        toks, final = [], None
        for line in r:
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[len(b"data: "):])
            check("error" not in ev, f"stream error event: {ev}")
            if ev.get("done"):
                final = ev["generated_tokens"]
            else:
                check(ev["index"] == len(toks), f"stream order: {ev}")
                toks.append(ev["token"])
        check(final is not None and toks == final,
              f"streamed tokens {toks} != done frame {final}")
        return final


def _agreement(ref: list, got: list) -> tuple:
    """Teacher-forced greedy agreement: the positions of each stream up to
    and including its first divergence from the reference — up to there
    both sides saw the same context, after it they answer different
    questions. (Position 0 comes from the prefill, which only the int8
    pool changes; a decode kernel that is wrong diverges at position 1 of
    every stream and scores 1/2.)"""
    matched = total = 0
    for r, g in zip(ref, got):
        check(len(g) == len(r), f"{len(g)} tokens for {len(r)} asked")
        div = next((i for i, (a, b) in enumerate(zip(r, g)) if a != b),
                   None)
        matched += len(r) if div is None else div
        total += len(r) if div is None else div + 1
    return matched, total


def phase_serve(sz: dict, n_dev: int, dry: bool) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from fedml_tpu.llm.transformer import TransformerLM
    from fedml_tpu.serving.scheduler import start_replica
    from fedml_tpu.utils import xla_ledger

    sv, lm = sz["serve"], sz["lm"]
    out = {"kernel_vs_gather_rel_err": _kernel_vs_gather(sz)}

    params = _lm_params(TransformerLM(**lm, scan_layers=True), sz["dtype"])
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, lm["vocab_size"], n).tolist()
               for n in sv["prompt_lens"]]
    # one repetitive prompt, so the n-gram speculator has drafts to offer
    rep = sv["prompt_lens"][1]
    prompts[1] = (prompts[1][:sv["page"]] * rep)[:rep]
    bodies = [{"tokens": p, "max_new_tokens": sv["new"],
               **({"stream": True} if i in (0, 3) else {})}
              for i, p in enumerate(prompts)]

    def replica(name, knobs, which):
        """Bring one replica up, serve bodies[which] concurrently, check the
        ENGINE served them through the programs its knobs call for, tear it
        down. Returns the token lists."""
        spec = "spec_decode" in knobs
        program = "engine_spec" if spec else "engine_step"
        c0 = _counters()
        xla_ledger.reset()      # the kernels read below are THIS replica's
        _job, runner = start_replica({
            "model_kind": "lm", "lm": {**lm, "scan_layers": True},
            "params": params, "port": 0,
            "serve": {"decode_slots": sv["slots"],
                      "engine_max_len": sv["max_len"],
                      "kv_page_size": sv["page"],
                      "prefill_chunk": sv["chunk"], **knobs}})
        try:
            url = f"http://127.0.0.1:{runner.port}/predict"
            with ThreadPoolExecutor(len(which)) as pool:
                toks = list(pool.map(lambda i: _post(url, bodies[i]),
                                     which))
            counts = runner.predictor.engine.program_counts()
        finally:
            runner.stop()
        del runner
        gc.collect()
        c1 = _counters()
        delta = lambda k: c1.get(k, 0) - c0.get(k, 0)
        check(delta("serving.engine.completions") == len(which)
              and delta("serving.engine.errors") == 0,
              f"{name}: the engine completed "
              f"{delta('serving.engine.completions')} of {len(which)} "
              f"requests ({delta('serving.engine.errors')} engine errors) — "
              "the rest were answered by the per-request fallback")
        for tk in toks:
            check(len(tk) == sv["new"]
                  and all(0 <= x < lm["vocab_size"] for x in tk),
                  f"{name}: bad generation {tk}")
        # one decode program: the plain step, or — speculating — the verify
        # window and NO plain step
        check(counts["step"] == (0 if spec else 1)
              and counts.get("verify") == (1 if spec else None),
              f"{name}: decode programs {counts}")
        kernels = (xla_ledger.programs().get(program) or {}).get("kernels")
        if not dry:
            check(kernels == (["paged_attention"] if knobs["paged_kernel"]
                              else []),
                  f"{name}: Mosaic kernels of {program}: {kernels}")
        out[name] = {"programs": counts, "mosaic_kernels": kernels}
        if spec:
            check(delta("serving.spec.proposed") > 0,
                  f"{name}: nothing was drafted")
            out[name]["accepted/proposed"] = (
                f"{delta('serving.spec.accepted')}"
                f"/{delta('serving.spec.proposed')}")
        return toks

    def agree(name, ref, got):
        m, n = _agreement(ref, got)
        out[name]["agreement"] = f"{m}/{n}"
        check(n > 0 and m / n >= AGREEMENT_FLOOR,
              f"{name}: teacher-forced greedy agreement with the reference "
              f"{m}/{n} < {AGREEMENT_FLOOR}")

    everyone = list(range(len(prompts)))
    ref = replica("gather", {"paged_kernel": False}, everyone)
    got = replica("kernel", {"paged_kernel": True}, everyone)
    agree("kernel", ref, got)
    agree("kernel_int8", ref, replica(
        "kernel_int8", {"paged_kernel": True, "kv_quant": "int8"}, everyone))
    # speculation must emit the same engine's own non-speculative stream
    agree("kernel_spec", [got[1]], replica(
        "kernel_spec", {"paged_kernel": True, "spec_decode": "ngram",
                        "spec_k": sv["spec_k"]}, [1]))
    if n_dev > 1 and lm["n_heads"] % n_dev == 0:
        # tensor parallel over every chip: the paged kernel shard_mapped
        # over the pool's heads axis
        agree(f"kernel_mp{n_dev}", [ref[0], ref[2]], replica(
            f"kernel_mp{n_dev}", {"paged_kernel": True, "engine_mp": n_dev},
            [0, 2]))
    return out


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny shapes, interpreted kernels, on the CPU; "
                         "debugs this script, proves nothing about a chip")
    args = ap.parse_args(argv)
    dry = args.dry_run_cpu
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # a hang must not outlive the 1200 s the contract allows: dump every
    # thread's stack and die
    faulthandler.dump_traceback_later(1150, exit=True)

    import jax

    from fedml_tpu import native
    from fedml_tpu.utils import enable_compilation_cache
    from fedml_tpu.utils.flops import tpu_spec_peak_tflops

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if not dry:
        if dev.platform != "tpu":
            print(f"chip_smoke: jax found platform {dev.platform!r} "
                  f"({dev.device_kind}); this check needs a TPU "
                  "(--dry-run-cpu debugs the script itself)",
                  file=sys.stderr)
            return 2
        if tpu_spec_peak_tflops(dev) is None:
            print(f"chip_smoke: device kind {dev.device_kind!r} is not in "
                  "the peak table (fedml_tpu/utils/flops.py) — an unknown "
                  "chip is an error here, not a default", file=sys.stderr)
            return 2
    cache_dir = enable_compilation_cache()
    entries = lambda: (len(os.listdir(cache_dir))
                       if os.path.isdir(cache_dir) else 0)
    entries0 = entries()
    clock = CompileClock()
    sz = SIZES[dry]
    log(f"{dev.platform} {dev.device_kind} x{n_dev}  jax {jax.__version__}"
        f"  cache {cache_dir} ({entries0} entries)"
        + ("  DRY RUN (cpu, tiny shapes, interpreted kernels)" if dry else ""))

    phases: dict = {}
    for name, fn in (("round", lambda: phase_round(sz, n_dev, dry)),
                     ("fedllm", lambda: phase_fedllm(sz, dry)),
                     ("serve", lambda: phase_serve(sz, n_dev, dry))):
        t0, c0 = time.perf_counter(), clock.total
        try:
            row = {"ok": True, **fn()}
        except Exception as e:  # noqa: BLE001 — recorded; exit code 1 below
            traceback.print_exc()
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        row["wall_s"] = round(time.perf_counter() - t0, 1)
        row["compile_s"] = round(clock.total - c0, 1)
        phases[name] = row
        log(f"{name}: {'ok' if row['ok'] else 'FAILED'}  wall "
            f"{row['wall_s']}s  compile {row['compile_s']}s  "
            + json.dumps({k: v for k, v in row.items()
                          if k not in ("ok", "wall_s", "compile_s")}))
        gc.collect()
    ok = all(p["ok"] for p in phases.values())
    report = {
        "ok": ok, "platform": dev.platform, "device_kind": dev.device_kind,
        "n_devices": n_dev, "jax": jax.__version__, "dry_run": dry,
        "native_available": native.available(),
        "compile_s": round(clock.total, 1),
        "cache": {"dir": cache_dir, "entries_before": entries0,
                  "entries_after": entries()},
        "phases": phases,
    }
    log("report " + json.dumps(report))
    # the LAST line is the verdict and nothing else: exactly these keys,
    # the device as jax reports it
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": n_dev}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
