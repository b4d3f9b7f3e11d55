"""The window kernel (`paged_attention_window`) ALONE on the chip, at the
step shapes of `smallthinker_decode_mixed16k`: 32 slots, one query a slot,
28 query heads over 4 KV heads of 128, each slot's ring of 289 pages of 16
in a bfloat16 pool, window 4,096 (PERF.md section 6).

For each ring block size asked for (and the kernel of another tree, given
by the path of its `ops/paged_attention.py`, as a baseline) it runs the
kernel over cases of L live slots at positions drawn from `--seed`: past
the window at every phase of the ring ("full"), or short of it ("short"),
the other slots retired. Every variant's rows are checked against attention
over the same keys gathered on the host in float32; then `--reps` calls
chained inside one jitted loop (each call's output is the next one's
query) are timed together, so the time is the kernel's and not the
dispatch's. Reported per case: microseconds an event, a live slot's share
of it, and the share of the least time the window's K and V bytes need at
819 GB/s. Needs a TPU (`--rehearse` runs the control flow at tiny sizes on
the CPU, interpreted: no time it prints is a chip's).

    python scripts/window_kernel_bench.py --blocks 8,16,32,64 \\
        --baseline <parent tree>/fedml_tpu/ops/paged_attention.py
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from fedml_tpu.ops import paged_attention as pa  # noqa: E402

# slots, ring pages, page, KV heads, query heads, head dim, window
CHIP = (32, 289, 16, 4, 28, 128, 4096)
TINY = (4, 11, 4, 2, 4, 8, 32)
HBM_BYTES_S = 819e9


def positions(kind, live, slots, window, seed):
    """pos [slots] and active [slots]: `live` slots drawn at random, past
    the window ("full", any phase of the ring) or short of it."""
    rng = np.random.default_rng(seed)
    active = np.zeros(slots, bool)
    active[rng.choice(slots, live, replace=False)] = True
    lo, hi = (window, 4 * window) if kind == "full" else (1, window - 1)
    return rng.integers(lo, hi, slots).astype(np.int32), active


def gathered(q, k, v, table, pos, active, ring, ps, window):
    """float32 attention of each live slot's one query over the keys
    pos - window + 1 .. pos gathered from its ring on the host."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    out = np.zeros(q.shape, np.float32)
    group = q.shape[2] // k.shape[2]
    for s in np.nonzero(active)[0]:
        keys = np.arange(max(0, pos[s] - window + 1), pos[s] + 1)
        pages = table[s, (keys // ps) % ring]
        kk, vv = k[pages, keys % ps], v[pages, keys % ps]  # [n, KV, Dh]
        for h in range(q.shape[2]):
            sc = kk[:, h // group] @ q[s, 0, h] * q.shape[-1] ** -0.5
            p = np.exp(sc - sc.max())
            out[s, 0, h] = p / p.sum() @ vv[:, h // group]
    return out


def chained(mod, reps, window, interpret):
    def run(q, k, v, table, pos, active):
        return jax.lax.fori_loop(0, reps, lambda _i, x: mod.paged_attention(
            x, k, v, table, pos, active=active, window=window,
            interpret=interpret), q)
    return jax.jit(run)


def timed(fn, args, repeats=3) -> float:
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best.append(time.perf_counter() - t)
    return float(np.median(best))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="8,16,32,64")
    ap.add_argument("--baseline", default=None,
                    help="another tree's ops/paged_attention.py")
    ap.add_argument("--live", default="0,1,2,4,8,32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--out", default=None,
                    help="also write the report to this JSON file")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --rehearse)")
    slots, ring, ps, kv, heads, dh, window = TINY if a.rehearse else CHIP
    reps = 2 if a.rehearse else a.reps
    variants = [(f"block{b}", pa, b) for b in map(int, a.blocks.split(","))]
    if a.baseline:
        spec = importlib.util.spec_from_file_location("baseline_pa",
                                                      a.baseline)
        base = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(base)
        variants.insert(0, ("baseline", base, None))
    keys = jax.random.split(jax.random.key(a.seed), 3)
    n_pages = 1 + slots * ring
    k = jax.random.normal(keys[0], (n_pages, ps, kv, dh), jnp.bfloat16)
    v = jax.random.normal(keys[1], (n_pages, ps, kv, dh), jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, 1, heads, dh), jnp.bfloat16)
    # the step's ring table: consecutive pool pages a slot (llm/decode.py)
    table = (1 + np.arange(slots)[:, None] * ring
             + np.arange(ring)[None]).astype(np.int32)
    cases = [("full", int(n)) for n in a.live.split(",") if int(n) <= slots]
    cases.append(("short", min(4, slots)))
    report = {"device": jax.devices()[0].device_kind, "seed": a.seed,
              "reps": reps, "shapes": dict(zip(
                  ("slots", "ring", "page", "kv", "heads", "dh", "window"),
                  (slots, ring, ps, kv, heads, dh, window))), "cases": {}}
    drawn = {}
    for kind, live in cases:
        pos, active = positions(kind, live, slots, window, a.seed + live)
        n_keys = int(np.minimum(pos[active] + 1, window).sum())
        drawn[f"{kind}{live}"] = (pos, active, gathered(
            q, k, v, table, pos, active, ring, ps, window))
        report["cases"][f"{kind}{live}"] = {
            "live": live, "keys": n_keys,
            # K and V of every attended key read once, bf16, over 819 GB/s
            "least_us": n_keys * kv * dh * 2 * 2 / HBM_BYTES_S * 1e6,
            "walks": [list(pa.ring_walk(int(p), 1, window, ps, ring))
                      for p in pos[active]][:4]}
    for name, mod, block in variants:
        if block is not None:
            mod._WINDOW_BLOCK_PAGES = block
            jax.clear_caches()
        fn = chained(mod, reps, window, a.rehearse)
        for case, (pos, active, want) in drawn.items():
            row = report["cases"][case]
            args = (q, k, v, table, pos, active)
            got = np.asarray(mod.paged_attention(
                *args[:5], active=active, window=window,
                interpret=a.rehearse), np.float32)
            us = timed(fn, args) / reps * 1e6
            row[name] = {"us_event": us,
                         "us_live_slot": us / row["live"] if row["live"]
                         else None,
                         "roofline_pct": 100 * row["least_us"] / us,
                         "max_abs_err_vs_gather": float(
                             np.abs(got - want).max())}
            print(json.dumps({"case": case, "variant": name, **row[name]}),
                  flush=True)
    if a.out:
        pathlib.Path(a.out).write_text(json.dumps(report, indent=1))
    print(json.dumps(report))


if __name__ == "__main__":
    main()
