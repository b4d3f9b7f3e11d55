"""The expert layer's row moves ALONE on the chip, at the sparse training
cell's shapes (PERF.md section 6, PR 35).

8,192 tokens x top 8 = a row buffer of 65,536 rows of 6,144 bfloat16, of
which `--live` rows hold a pair routed here (8,192 is the cell's even share;
0 and 65,536 are the ends). Each of `ops/routed_rows.py`'s moves is checked
against its gather form on the same inputs, then both are timed. `--rows` /
`--tokens` time the kernels at other block sizes. Needs a TPU: a CPU run
would time the Pallas interpreter.

    python scripts/routed_rows_bench.py --live 0,8192,65536 --rows 256,1024
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from fedml_tpu.llm import moe  # noqa: E402
from fedml_tpu.ops import routed_rows as rr  # noqa: E402

N, K, D, F = 8192, 8, 6144, 2048
P = N * K


def routing(n_live: int, seed: int):
    """(order, inv, here): `n_live` random pairs are routed here; `order`
    lists them first (a stable sort by not-here), `inv` is its inverse."""
    rng = np.random.default_rng(seed)
    here = np.zeros(P, bool)
    here[rng.choice(P, n_live, replace=False)] = True
    order = np.argsort(~here, kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    return (jnp.asarray(order), jnp.asarray(inv.reshape(N, K)),
            jnp.asarray(here.reshape(N, K)))


def gather_forms():
    """What llm/moe.py did before PR 35, move by move."""
    def out(x, order, n, scale=None):
        rows = x[order // K]
        return rows if scale is None else (
            scale[:, None] * rows.astype(jnp.float32)).astype(x.dtype)

    def back(src, inv, ok, w):
        z = jnp.where(ok[..., None], src[inv], 0).astype(jnp.float32)
        return jnp.einsum("nk,nkd->nd", w, z).astype(src.dtype)

    def dots(src, inv, ok, dy):
        z = jnp.where(ok[..., None], src[inv], 0).astype(jnp.float32)
        return jnp.einsum("nd,nkd->nk", dy.astype(jnp.float32), z)
    return jax.jit(out), jax.jit(back), jax.jit(dots)


def experts_elementwise(n, seed: int) -> dict:
    """The grouped product's elementwise neighbours over the buffer: the
    jnp forms (all P rows) against `live_map`'s (the live rows' blocks)."""
    keys = jax.random.split(jax.random.key(seed + 1), 4)
    gate, up, dact = (jax.random.normal(k, (P, F), jnp.bfloat16)
                      for k in keys[:3])
    g1 = jax.random.normal(keys[3], (P, D), jnp.bfloat16)
    plain = jax.jit(lambda g, u: jax.nn.silu(g) * u)
    plain_bwd = jax.jit(lambda g, u, d: jax.vjp(
        lambda g, u: jax.nn.silu(g) * u, g, u)[1](d))
    swiglu_bwd = jax.jit(lambda g, u, d, m: jax.vjp(
        lambda g, u: moe._swiglu(g, u, m), g, u)[1](d))
    return {"swiglu": [timed(plain, gate, up),
                       timed(jax.jit(moe._swiglu), gate, up, n)],
            "swiglu_bwd": [timed(plain_bwd, gate, up, dact),
                           timed(swiglu_bwd, gate, up, dact, n)],
            "add": [timed(jax.jit(jnp.add), g1, g1),
                    timed(moe._twice_bwd, n, (g1, g1))]}


def timed(fn, *args, reps: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="8192")
    ap.add_argument("--rows", default="")
    ap.add_argument("--tokens", default="")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    keys = jax.random.split(jax.random.key(a.seed), 4)
    x = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
    src = jax.random.normal(keys[1], (P, D), jnp.bfloat16)
    w = jax.random.uniform(keys[2], (N, K), jnp.float32)
    scale = jax.random.uniform(keys[3], (P,), jnp.float32)
    g_out, g_back, g_dots = gather_forms()
    report = {"device": jax.devices()[0].device_kind, "shape": [N, K, D]}
    sizes = [(r, rr._TOKENS) for r in [rr._ROWS] + [
        int(v) for v in a.rows.split(",") if v]] + [
        (rr._ROWS, int(v)) for v in a.tokens.split(",") if v]
    for n_live in (int(v) for v in a.live.split(",")):
        order, inv, here = routing(n_live, a.seed)
        n = jnp.int32(n_live)
        row: dict = {"gather_ms": {
            "out": timed(g_out, x, order, n),
            "out_scaled": timed(g_out, x, order, n, scale),
            "back": timed(g_back, src, inv, here, w),
            "dots": timed(g_dots, src, inv, here, x)}}
        for rows, tokens in sizes:
            rr._ROWS, rr._TOKENS = rows, tokens
            jax.clear_caches()
            tok = order // K
            got = {"out": rr.rows_out(x, tok, n),
                   "out_scaled": rr.rows_out(x, tok, n, scale),
                   "back": rr.rows_back(src, inv, here, w, n),
                   "dots": rr.rows_dots(src, inv, here, x, n)}
            want = {"out": g_out(x, order, n),
                    "out_scaled": g_out(x, order, n, scale),
                    "back": g_back(src, inv, here, w),
                    "dots": g_dots(src, inv, here, x)}
            gaps = {}
            for name in got:
                live = n_live if name.startswith("out") else N
                a_, b_ = (np.asarray(v[:live], np.float32)
                          for v in (got[name], want[name]))
                gaps[name] = float(np.max(np.abs(a_ - b_), initial=0.0)
                                   / max(float(np.max(np.abs(b_),
                                                      initial=0.0)), 1e-9))
            row[f"rows{rows}_tokens{tokens}"] = {
                "gap": gaps,
                "ms": {"out": timed(rr.rows_out, x, tok, n),
                       "out_scaled": timed(rr.rows_out, x, tok, n, scale),
                       "back": timed(rr.rows_back, src, inv, here, w, n),
                       "dots": timed(rr.rows_dots, src, inv, here, x, n),
                       "to_slabs": timed(jax.jit(
                           lambda s, m: rr._to_slabs(s, m, False)), src, n),
                       "reshape": timed(jax.jit(
                           lambda v: v.reshape(N, D // 128, 128) + 0), x)}}
        row["elementwise_ms_plain_live"] = experts_elementwise(n, a.seed)
        report[f"live{n_live}"] = row
        print(json.dumps({f"live{n_live}": row}), flush=True)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
