"""The expert layer's three grouped products ALONE on the chip, at the row
tiles 128, 256 and 512, at the shapes of the decode cells' expert layers
(PERF.md section 6, PR 37).

- `block`: SDAR's block forward, 16 slots x 4 positions x top 8 = a buffer of
  512 rows, 80 of them live over 26 of 128 experts (d 2,048, f 768);
- `chunk`: SDAR's admission chunk, 256 tokens x 8 = 2,048 rows over 128
  experts, every row live, the experts' load skewed;
- `glm5_chunk`: GLM-5's admission chunk, 512 tokens x 8 = 4,096 rows, 16 of
  256 experts held (d 6,144, f 2,048), 160 rows routed here.

Group sizes are drawn from `--seed` (a skewed popularity over the experts,
each token's top 8 distinct). Each tile's outputs are checked against the
512-row tile's (the tile changes which rows are thrown away, not the sums),
then the layer's gate, up, SwiGLU and down are timed together. Needs a TPU:
a CPU run would time the Pallas interpreter (`--rehearse` runs the control
flow at tiny widths there).

    python scripts/gmm_tile_bench.py --tiles 128,256,512
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
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from fedml_tpu.llm import moe  # noqa: E402

# name: (rows P, experts held G, router width E, d, f, top k, live rows)
SHAPES = {"block": (512, 128, 128, 2048, 768, 8, 80),
          "chunk": (2048, 128, 128, 2048, 768, 8, 2048),
          "glm5_chunk": (4096, 16, 256, 6144, 2048, 8, 160)}


def draw_sizes(p, g, e, k, live, seed):
    """[g] rows a held expert: tokens pick k distinct experts of e from a
    skewed popularity until `live` picks land on the first g."""
    rng = np.random.default_rng(seed)
    pop = np.exp(2.5 * rng.standard_normal(e))
    sizes = np.zeros(g, np.int64)
    while sizes.sum() < live:
        for x in rng.choice(e, k, replace=False, p=pop / pop.sum()):
            if x < g and sizes.sum() < live:
                sizes[x] += 1
    return jnp.asarray(sizes, jnp.int32)


def layer(tm, interpret):
    """The expert layer's products at row tile `tm`, as llm/moe.py has
    them (tiles (tm, min(K, 1024), min(N, 1024)))."""
    def gmm(x, w, sizes):
        tiles = (tm, min(x.shape[1], moe.GMM_TILES[1]),
                 min(w.shape[-1], moe.GMM_TILES[2]))
        return megablox.gmm(x, w, sizes, x.dtype, tiles, interpret=interpret)

    def run(x, wg, wu, wd, sizes):
        gate, up = gmm(x, wg, sizes), gmm(x, wu, sizes)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(x.dtype)
        return gmm(act, wd, sizes)
    return jax.jit(run)


def timed(fn, *args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --rehearse)")
    tiles = [int(v) for v in a.tiles.split(",")]
    report = {"device": jax.devices()[0].device_kind, "seed": a.seed}
    for name in a.shapes.split(","):
        p, g, e, d, f, k, live = SHAPES[name]
        if a.rehearse:
            d, f = 256, 128
        sizes = draw_sizes(p, g, e, k, live, a.seed)
        keys = jax.random.split(jax.random.key(a.seed), 4)
        x = jax.random.normal(keys[0], (p, d), jnp.bfloat16)
        wg, wu = (jax.random.normal(kk, (g, d, f), jnp.bfloat16) / d ** 0.5
                  for kk in keys[1:3])
        wd = jax.random.normal(keys[3], (g, f, d), jnp.bfloat16) / f ** 0.5
        n_live = int(jnp.sum(sizes > 0))
        ref = np.asarray(layer(512, a.rehearse)(x, wg, wu, wd, sizes)[:live],
                         np.float32)
        row = {"p": p, "held": g, "rows_live": live, "experts_live": n_live,
               "largest": int(jnp.max(sizes)), "tiles": {}}
        for tm in tiles:
            fn = layer(tm, a.rehearse)
            got = np.asarray(fn(x, wg, wu, wd, sizes)[:live], np.float32)
            ms = timed(fn, x, wg, wu, wd, sizes,
                       reps=1 if a.rehearse else a.reps)
            visited = int(moe.tile_rows(sizes, tm)) // tm
            row["tiles"][tm] = {
                "ms": ms, "max_abs_diff_vs_512": float(np.max(np.abs(
                    got - ref), initial=0.0)),
                "tiles_visited": visited,
                "us_per_visit_per_product": ms * 1e3 / (3 * visited)}
        report[name] = row
        print(json.dumps({name: row}), flush=True)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
