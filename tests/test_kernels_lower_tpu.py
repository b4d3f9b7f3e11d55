"""Every Pallas kernel variant the trainer and the engine can request must
LOWER FOR THE TPU — checked here, on the CPU, in seconds.

tier-1 runs the kernels under `interpret=True`, which never meets the TPU
lowering rules: the int8 paged kernel shipped with a (1, H) block over a
[P, H] array — illegal on a TPU (the last two block dims must be (8, 128)-
tiled or full) — and nothing failed until someone looked. Two nets:

- `jax.export` for `platforms=["tpu"]` with `interpret=False` runs the Pallas
  TPU lowering (block-shape rules, unsupported primitives) and must leave a
  `tpu_custom_call` per kernel in the module;
- where libtpu is installed, the same programs are COMPILED for a v5e
  through a device-less topology — the real Mosaic compiler, no chip. (It
  cannot run them: numerics on the chip are `chip_smoke.py`'s job.)

Shapes are chip_smoke.py's: flash at [B*H, T, Dh] = [64, 2048, 128]; paged
at S=8 slots, H=16, Dh=128, page 16, 2048-token tables, and once at the
serving cell's 16 slots.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.ops.paged_attention import (
    index_scores, latent_attention, latent_block_pages, paged_attention,
)
from fedml_tpu.ops.routed_rows import rows_back, rows_dots, rows_out

S = jax.ShapeDtypeStruct
BH, T, DH = 64, 2048, 128
SLOTS, HEADS, PAGE, MAX_PAGES = 8, 16, 16, 128
N_PAGES = SLOTS * MAX_PAGES + 1


def _flash_loss(q, k, v):
    o = flash_attention(q, k, v, interpret=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def _flash_case(kind, dtype):
    x = S((BH, T, DH), dtype)
    if kind == "fwd":
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                (x, x, x), {"flash_fwd"})
    grad = jax.grad(_flash_loss, argnums=(0, 1, 2))
    if kind == "fwd_bwd":
        return grad, (x, x, x), {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    xv = S((2,) + x.shape, dtype)        # the round engine's client vmap
    return (jax.vmap(grad), (xv, xv, xv),
            {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})


def _paged_case(c, dtype, quant, slots=SLOTS):
    """`active` rides along as the decode step passes it (llm/decode.py)."""
    n_pages = slots * MAX_PAGES + 1
    q = S((slots, c, HEADS, DH), dtype)
    pool = S((n_pages, PAGE, HEADS, DH), jnp.int8 if quant else dtype)
    pages, pos = S((slots, MAX_PAGES), jnp.int32), S((slots,), jnp.int32)
    active = S((slots,), jnp.bool_)
    if not quant:
        return (lambda q, k, v, pg, po, act: paged_attention(
            q, k, v, pg, po, active=act, interpret=False),
            (q, pool, pool, pages, pos, active), {"paged_attention"})
    sc = S((n_pages, HEADS), jnp.float32)
    return (lambda q, k, v, pg, po, ks, vs, act: paged_attention(
        q, k, v, pg, po, ks, vs, active=act, interpret=False),
        (q, pool, pool, pages, pos, sc, sc, active), {"paged_attention"})


def _window_case(c):
    """The window kernel at SmallThinker's widths: 32 slots, 28 query heads
    over 4 KV heads of 128, each slot's ring of 289 pages of 16 (a window
    of 4,096 beside a prefill chunk of 512)."""
    slots, ring = 32, 289
    q = S((slots, c, 28, DH), jnp.bfloat16)
    pool = S((1 + slots * ring, PAGE, 4, DH), jnp.bfloat16)
    return (lambda q, k, v, pg, po, act: paged_attention(
        q, k, v, pg, po, active=act, window=4096, interpret=False),
        (q, pool, pool, S((slots, ring), jnp.int32), S((slots,), jnp.int32),
         S((slots,), jnp.bool_)), {"paged_attention_window"})


def _reglu_case(p):
    """The expert layer's gated unit over a buffer of p rows of 768 (top 6:
    6 and 12 rows are a prefill chunk of one and of two tokens, no whole
    multiple of 8 rows; 192 a decode step of 32 slots)."""
    from fedml_tpu.llm.moe import _reglu
    from fedml_tpu.ops import routed_rows

    rows = S((p, 768), jnp.bfloat16)

    def fn(gate, up, n):
        auto, routed_rows._auto_interpret = (routed_rows._auto_interpret,
                                             lambda: False)
        try:
            return _reglu(gate, up, n)
        finally:
            routed_rows._auto_interpret = auto
    return fn, (rows, rows, S((), jnp.int32)), {"moe_reglu"}


def _latent_case(kind, slots, c, n_pages):
    """The latent pool's two kernels at GLM-5's widths (64 heads over ONE
    640-wide row a token, 32 index heads of 128), page 16: a decode step of
    16 slots over the whole 2,048-page table and a 512-token prefill chunk."""
    n_pool, block = 16 * 2048 + 1, latent_block_pages(n_pages)
    pages, live = S((slots, n_pages), jnp.int32), S((slots,), jnp.int32)
    if kind == "index":
        return (lambda q, w, k, pg, lv: index_scores(
            q, w, k, pg, lv, interpret=False),
            (S((slots, c, 32, 128), jnp.bfloat16),
             S((slots, c, 32), jnp.float32),
             S((n_pool, PAGE, 128), jnp.bfloat16), pages, live),
            {"index_scores"})
    bias = S((slots, n_pages // block, c, block * PAGE), jnp.float32)
    return (lambda q, kv, pg, lv, b: latent_attention(
        q, kv, pg, lv, b, 512, interpret=False),
        (S((slots, c, 64, 640), jnp.bfloat16),
         S((n_pool, PAGE, 640), jnp.bfloat16), pages, live, bias),
        {"latent_attention"})


def _routed_case(kind, n):
    """The expert layer's row moves at K-EXAONE's and GLM-5's width (rows
    of 6,144 bfloat16, top 8): the training cell's 8,192 tokens a silo, a
    512-token prefill chunk, a decode step's 16 slots."""
    k, d = 8, 6144
    x = S((n, d), jnp.bfloat16)
    buf = S((n * k, d), jnp.bfloat16)
    pairs, live = S((n, k), jnp.int32), S((), jnp.int32)
    if kind == "out":
        return (lambda x, i, m, s: rows_out(x, i, m, s, interpret=False),
                (x, S((n * k,), jnp.int32), live, S((n * k,), jnp.float32)),
                {"moe_rows_out"})
    ok = S((n, k), jnp.bool_)
    if kind == "back":
        return (lambda b, i, o, w, m: rows_back(b, i, o, w, m,
                                                interpret=False),
                (buf, pairs, ok, S((n, k), jnp.float32), live),
                {"moe_to_slabs", "moe_rows_back"})
    return (lambda b, i, o, dy, m: rows_dots(b, i, o, dy, m,
                                             interpret=False),
            (buf, pairs, ok, x, live), {"moe_to_slabs", "moe_rows_dots"})


CASES = {
    "routed_out_silo": lambda: _routed_case("out", 8192),
    "routed_back_silo": lambda: _routed_case("back", 8192),
    "routed_dots_silo": lambda: _routed_case("dots", 8192),
    "routed_out_step": lambda: _routed_case("out", 16),
    "routed_back_chunk": lambda: _routed_case("back", 512),
    "latent_index_step": lambda: _latent_case("index", 16, 1, 2048),
    "latent_index_chunk": lambda: _latent_case("index", 1, 512, 2048),
    "latent_attend_step": lambda: _latent_case("attend", 16, 1, 2048),
    "latent_attend_chunk": lambda: _latent_case("attend", 1, 512, 512),
    "flash_fwd_bf16": lambda: _flash_case("fwd", jnp.bfloat16),
    "flash_fwd_bwd_bf16": lambda: _flash_case("fwd_bwd", jnp.bfloat16),
    "flash_fwd_bwd_f32": lambda: _flash_case("fwd_bwd", jnp.float32),
    "flash_vmap_fwd_bwd_bf16": lambda: _flash_case("vmap", jnp.bfloat16),
    "paged_c1_bf16": lambda: _paged_case(1, jnp.bfloat16, False),
    "paged_c4_bf16": lambda: _paged_case(4, jnp.bfloat16, False),
    "paged_c1_f32": lambda: _paged_case(1, jnp.float32, False),
    "paged_c4_f32": lambda: _paged_case(4, jnp.float32, False),
    "paged_c1_int8": lambda: _paged_case(1, jnp.bfloat16, True),
    "paged_c4_int8": lambda: _paged_case(4, jnp.bfloat16, True),
    # the serving cell's own step (BENCHMARK.json olmo1b_decode_chat)
    "paged_c1_bf16_s16": lambda: _paged_case(1, jnp.bfloat16, False, slots=16),
    # a window layer's step (smallthinker_decode_mixed16k)
    "paged_window_c1_s32": lambda: _window_case(1),
    "reglu_rows6": lambda: _reglu_case(6),
    "reglu_rows12": lambda: _reglu_case(12),
    "reglu_rows192": lambda: _reglu_case(192),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu(name):
    fn, args, kernels = CASES[name]()
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()
    assert "tpu_custom_call" in text
    # the stable kernel names the XLA ledger / chip_smoke.py read
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == kernels


@pytest.fixture(scope="module")
def v5e():
    """One device of a device-less v5e topology (libtpu's compile-only
    client); skips where libtpu is not installed."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no TPU compile-only topology: {type(e).__name__}: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", [
    n if n in ("flash_fwd_bwd_bf16", "paged_c1_bf16_s16", "paged_c4_int8",
               "latent_index_step", "latent_attend_step",
               "latent_attend_chunk", "routed_out_silo", "routed_back_silo",
               "routed_dots_silo", "paged_window_c1_s32", "reglu_rows6",
               "reglu_rows12")
    else pytest.param(n, marks=pytest.mark.slow)    # tier-1 is at its cap
    for n in sorted(CASES)])
def test_kernel_compiles_with_mosaic(name, v5e):
    fn, args, _kernels = CASES[name]()
    compiled = jax.jit(
        fn, in_shardings=jax.tree.map(lambda _: v5e, args)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_step(v5e, layers, d_ff):
    """The engine's decode step at chip_smoke.py's engine shape (8 slots x
    2,048, page 16, heads 16 x 128), over the tree the engine holds (wq, wk
    and wv laid out as one kernel, llm/decode.py `lay_out_blocks`), cache
    donated, compiled for the v5e. -> (compiled, pool)."""
    from fedml_tpu.llm.decode import lay_out_blocks, make_paged_kv_decode
    from fedml_tpu.llm.transformer import TransformerLM
    from fedml_tpu.ops import paged_attention as pa

    d_model = HEADS * DH
    model = TransformerLM(vocab_size=512, d_model=d_model, n_layers=layers,
                          n_heads=HEADS, d_ff=d_ff, scan_layers=True)
    shapes = jax.eval_shape(lambda: lay_out_blocks(model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    params = jax.tree.map(lambda a: S(a.shape, jnp.bfloat16), shapes)
    pool = S((layers, N_PAGES, PAGE, HEADS, DH), jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    vec = S((SLOTS,), jnp.int32)
    args = (params, cache, S((SLOTS, MAX_PAGES), jnp.int32), vec, vec,
            S((SLOTS,), jnp.bool_))
    _chunk, step, _verify, _cb = make_paged_kv_decode(
        HEADS, PAGE, dtype=jnp.bfloat16, kernel=True)
    auto, pa._auto_interpret = pa._auto_interpret, lambda: False
    try:        # the backend here is the CPU; the program is the chip's
        compiled = jax.jit(
            lambda p, c, *a: step(p, None, c, *a), donate_argnums=(1,),
            in_shardings=jax.tree.map(lambda _: v5e, args)).lower(
                *args).compile()
    finally:
        pa._auto_interpret = auto
    assert "tpu_custom_call" in compiled.as_text()
    return compiled, pool


def test_paged_step_carries_the_pool_in_place(v5e):
    """The program's temporaries stay under a quarter of the pool's bytes.
    With the pool as the layer scan's xs/ys they were 1.1 pools — the
    restacked copy — and each step moved the pool three times (ISSUE 27)."""
    compiled, pool = _engine_step(v5e, layers=4, d_ff=512)
    pool_bytes = 2 * math.prod(pool.shape) * pool.dtype.itemsize   # K and V
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


def test_paged_step_reads_the_layers_weights_in_place(v5e):
    """No layer's [d, d]-sized weight is cut out of the stack into a buffer
    of its own, nor copied into another layout, before a product reads it.
    Over three separate stacked wq/wk/wv kernels the compiled step held, a
    layer, one `constant_dynamic-slice_fusion` and one `copy` of each
    (PERF.md section 6: 1.04 ms of the dense serving cell's 4.27 ms step
    on a TPU v5e)."""
    compiled, _pool = _engine_step(v5e, layers=2, d_ff=HEADS * DH)
    d = HEADS * DH
    per_layer = re.compile(
        rf"= bf16\[1,{d},(?:\d+,)*\d+\]\{{[^}}]*\}} "
        r"(?:fusion|copy)\(")
    moved = [ln.strip()[:120] for ln in compiled.as_text().splitlines()
             if per_layer.search(ln)]
    assert not moved, moved
