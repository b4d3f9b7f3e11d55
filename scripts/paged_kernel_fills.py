"""The paged-attention kernel ALONE on the chip, at the serving cell's shapes
and three fills of the page table (PERF.md section 6, PR 31).

One decode step's worth of kernel calls: 16 layers over a flat pool
`[L * P, page, H, Dh]` with page ids offset by `l * P`, as the decode layer
scan calls it (S 16, max_pages 128, page 16, H 16, Dh 128). Fills: 2 slots
live at 300 tokens, 16 at 300, 16 at 2,048. Any number of trees can be timed
side by side (`--tree _parent --tree .`), each kernel loaded from its file, so
a parent and a change meet the same inputs on the same chip (`--tree gather`
is the gather path's attention, bf16 pools only); `--blocks` times the last
tree at other pages-a-block values. Needs a TPU: a CPU run would time
the Pallas interpreter.

    python scripts/paged_kernel_fills.py --tree _parent --tree . --blocks 4,16
"""
from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

LAYERS, SLOTS, MAX_PAGES, PAGE, HEADS, DH = 16, 16, 128, 16, 16, 128
POOL_PAGES = SLOTS * MAX_PAGES + 1
FILLS = {"2x300": (2, 300), "16x300": (16, 300), "16x2048": (16, 2048)}


def load_kernel(tree: str):
    path = pathlib.Path(tree) / "fedml_tpu" / "ops" / "paged_attention.py"
    spec = importlib.util.spec_from_file_location(
        f"paged_attention_{abs(hash(str(path.resolve())))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs(c: int, quant: bool, seed: int):
    """Pools on the device, drawn there: 2 x 2.1 GB in bf16."""
    kk, kv, kq, ks = jax.random.split(jax.random.key(seed), 4)
    shape = (LAYERS * POOL_PAGES, PAGE, HEADS, DH)
    if quant:
        k = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        v = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        scales = tuple(jax.random.uniform(
            s, (LAYERS * POOL_PAGES, HEADS), jnp.float32, 0.002, 0.02)
            for s in jax.random.split(ks))
    else:
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        scales = ()
    q = jax.random.normal(kq, (SLOTS, c, HEADS, DH), jnp.bfloat16)
    return q, k, v, scales


def table(live: int, tokens: int, c: int):
    """Engine convention: a live slot holds ceil(tokens / page) pages of its
    own, entries past them (and retired slots' rows) name the null page 0."""
    pages = np.zeros((SLOTS, MAX_PAGES), np.int32)
    held = -(-tokens // PAGE)
    for s in range(live):
        pages[s, :held] = 1 + s * MAX_PAGES + np.arange(held)
    pos = np.where(np.arange(SLOTS) < live, tokens - c, 0).astype(np.int32)
    return pages, pos, np.arange(SLOTS) < live


class Gather:
    """The gather path's attention (llm/decode.py `verify` with
    `kernel=False`), restated: every slot's whole table row gathered into a
    virtually-contiguous sequence, then dense masked attention."""

    @staticmethod
    def paged_attention(q, k_pool, v_pool, pages, pos):
        kk = k_pool[pages].reshape(SLOTS, -1, HEADS, DH)
        vv = v_pool[pages].reshape(SLOTS, -1, HEADS, DH)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * DH ** -0.5
        posr = pos[:, None] + jnp.arange(q.shape[1])
        live = jnp.arange(kk.shape[1])[None, None, :] <= posr[:, :, None]
        s = jnp.where(live[:, None, :, :], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


def step_fn(module):
    takes_active = "active" in inspect.signature(
        module.paged_attention).parameters

    @jax.jit
    def step(q, k, v, pages, pos, active, *scales):
        def layer(acc, l):
            kw = {"active": active} if takes_active else {}
            o = module.paged_attention(q, k, v, l * POOL_PAGES + pages, pos,
                                       *scales, **kw)
            return acc + o.astype(jnp.float32), None
        acc, _ = jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                              jnp.arange(LAYERS, dtype=jnp.int32))
        return acc
    return step


def time_ms(fn, args, reps: int) -> float:
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / reps * 1e3)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", action="append", required=True)
    ap.add_argument("--blocks", default="",
                    help="other pages-a-block values for the LAST tree")
    ap.add_argument("--variants", default="c1_bf16,c4_bf16,c1_int8")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/paged_kernel_fills.json")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("paged_kernel_fills: needs a TPU "
                         f"(backend is {jax.default_backend()})")
    kernels = [(tree, Gather if tree == "gather" else load_kernel(tree), None)
               for tree in a.tree]
    last = kernels[-1]
    for b in filter(None, a.blocks.split(",")):
        kernels.append((f"{last[0]}@block{b}", load_kernel(last[0]), int(b)))
    rows = []
    for variant in a.variants.split(","):
        c, quant = int(variant[1]), variant.endswith("int8")
        q, k, v, scales = inputs(c, quant, a.seed)
        for fill, (live, tokens) in FILLS.items():
            pages, pos, active = table(live, tokens, c)
            args = (q, k, v, *map(jnp.asarray, (pages, pos, active))) + scales
            first = None
            for name, module, block in kernels:
                if block is not None:
                    module._BLOCK_PAGES = block
                fn = step_fn(module)
                ms = time_ms(fn, args, a.reps)
                out = np.asarray(fn(*args))[:live]
                first = out if first is None else first
                row = {"variant": variant, "fill": fill, "kernel": name,
                       "step_ms": round(ms, 4),
                       "max_abs_diff_vs_first": float(
                           np.abs(out - first).max()),
                       "finite": bool(np.isfinite(out).all())}
                rows.append(row)
                print(json.dumps(row), flush=True)
    pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(a.out).write_text(json.dumps(
        {"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))


if __name__ == "__main__":
    main()
