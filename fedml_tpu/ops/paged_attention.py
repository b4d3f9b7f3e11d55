"""Pallas paged-attention decode kernel — fused attention over the paged
KV pool, reading each slot's pages IN PLACE.

The gather-path paged step (llm/decode.py make_paged_kv_decode) first
materializes every slot's pages into a virtually-contiguous
[S, max_pages * page_size, H, Dh] sequence with an XLA gather, then runs
dense masked attention over it — per decode token that is one full copy
of each slot's context through HBM before a single FLOP of attention.
This kernel removes the copy: the device-side page table rides in as a
SCALAR-PREFETCH operand, the BlockSpec index map reads it to DMA exactly
one (page_size, H, Dh) K and V slab per grid step straight from the
pool, and a flash-style online softmax (running max m, running sum l,
o accumulator in VMEM scratch — the ops/flash_attention.py recurrence)
folds each page's contribution in as it streams. Per-token attention
HBM traffic drops from O(context copied + context read) to O(context
read), and the transient gather buffer disappears from the memory
high-water mark.

Shape contract (one transformer layer's pages; the decode layer scan
carries the whole pool flat as `[L * P, page_size, H, Dh]` and calls this
per layer with page ids offset by `l * P`, so "the pool" here is every
layer's and the page table picks this layer's slabs out of it in place):

    q      [S, C, H, Dh]   C queries per slot at global positions
                           pos[s] .. pos[s] + C - 1 (C == 1 is the plain
                           decode step; C > 1 is speculative verify)
    k/v    [P, page_size, H, Dh]   the persistent page pool
    pages  [S, max_pages] int32    page table rows (engine convention:
                           entries beyond a slot's reservation are 0,
                           the reserved null/trash page)
    pos    [S] int32       first query position per slot
    ->     [S, C, H, Dh]

With an int8 pool (`kv_quant: int8`), the per-(page, head) f32 scales
[P, H] are gathered through the page table OUTSIDE the kernel into
[S, H, max_pages] (S * max_pages * H floats — noise next to one slab)
and ride as two further operands blocked per slot: a (1, H) row of the
[P, H] array is not a legal TPU block (the last two block dims must be
(8, 128)-tiled or full), a whole (H, max_pages) slot view is. Each grid
step picks its page's [H, 1] column with a lane mask — heads already on
sublanes, which is where the (page_size, H, Dh) slab wants them — and
dequantizes the int8 slab in VMEM: the pool crosses HBM at one byte per
element, which is the whole point.

Semantics match the gather path exactly: query i of slot s attends
virtual positions <= pos[s] + i of the slot's page-table view (the
active-mask write redirect and the null-page-0 convention live in the
caller — writes land before attention, and positions past `pos` are
masked here, so null-page garbage is never read into a live result).
Pages entirely past a slot's last query are skipped with pl.when — their
MXU work is elided (the slab DMA still runs; for short slots the table
points those steps at page 0).

Grid: (S, max_pages); the page-grid dimension executes sequentially per
slot, so the (m, l, o) accumulators carry across it in VMEM scratch and
the output block (revisited every page step) is written once at the
final page. Scores/accumulation are f32; matmuls run in the input dtype
with f32 accumulation (bf16 pools keep full MXU rate).

CPU (tests / virtual meshes) runs the same kernel under
`interpret=True` automatically — the tier-1 identity pins in
tests/test_decode_kernel_spec.py exercise the REAL kernel body, with
the gather path kept as the oracle; the TPU path compiles through
Mosaic. Tensor-parallel serving shard_maps this call over the heads
axis (heads are independent in attention), which is how the engine's
`partition.paged_kv_cache_spec` layout reaches the kernel unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128  # scratch minor dim: the TPU lane count; m/l stay lane-broadcast


def _dot(a, b, contract, batch):
    """Per-head MXU dot with f32 accumulation (HIGHEST only for f32
    operands — same contract as ops/flash_attention._dot)."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (contract, batch),
        preferred_element_type=jnp.float32, precision=prec)


def _kernel(pages_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
            page_size: int, scale: float, quant: bool):
    if quant:
        # int8 pool: this slot's per-(head, page) scales [1, H, max_pages],
        # page-table-gathered by the caller
        ks_ref, vs_ref, o_ref, o_acc, m_acc, l_acc = rest
    else:
        o_ref, o_acc, m_acc, l_acc = rest
        ks_ref = vs_ref = None
    s_idx, pj = pl.program_id(0), pl.program_id(1)
    n_pb = pl.num_programs(1)
    pos = pos_ref[s_idx]
    c = q_ref.shape[1]

    @pl.when(pj == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG)
        l_acc[...] = jnp.zeros_like(l_acc)

    # pages entirely past the slot's LAST query position contribute nothing
    @pl.when(pj * page_size <= pos + c - 1)
    def _compute():
        q = q_ref[0]                                   # [C, H, Dh]
        kb = k_ref[0]                                  # [ps, H, Dh]
        vb = v_ref[0]
        if quant:
            # in-place dequant of the DMA'd slab: the pool stays int8 in
            # HBM and on the wire; f32 rows exist only in VMEM, cast to
            # the query dtype so the MXU contract matches the bf16 path.
            # This page's scale column comes out of the slot's
            # [H, max_pages] view by lane mask (a dynamic lane index is
            # not a Mosaic load; one nonzero term keeps the sum exact).
            here = jax.lax.broadcasted_iota(
                jnp.int32, ks_ref.shape[1:], 1) == pj
            ksc = jnp.where(here, ks_ref[0], 0.0).sum(axis=1, keepdims=True)
            vsc = jnp.where(here, vs_ref[0], 0.0).sum(axis=1, keepdims=True)
            kb = (kb.astype(jnp.float32) * ksc[None]).astype(q.dtype)
            vb = (vb.astype(jnp.float32) * vsc[None]).astype(q.dtype)
        # scores per head: batch H, contract Dh -> [H, C, ps]
        s = _dot(q, kb, ((2,), (2,)), ((1,), (1,))) * scale
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (1, c, 1), 1)
        vpos = pj * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        s = jnp.where(vpos <= qpos, s, _NEG)
        m = m_acc[:, :, :1]                            # [H, C, 1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l_acc[:, :, :1] * corr + p.sum(axis=-1, keepdims=True)
        # [H, C, ps] x [ps, H, Dh]: batch H, contract ps -> [H, C, Dh]
        o_acc[...] = o_acc[...] * corr + _dot(
            p.astype(vb.dtype), vb, ((2,), (0,)), ((0,), (1,)))
        m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
        l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(pj == n_pb - 1)
    def _finalize():
        l = jnp.maximum(l_acc[:, :, :1], 1e-30)
        o_ref[0] = jnp.moveaxis(o_acc[...] / l, 0, 1).astype(o_ref.dtype)


def _auto_interpret() -> bool:
    return jax.default_backend() not in ("tpu",)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q, k_pool, v_pool, pages, pos, scales, interpret: bool):
    s_, c, h, dh = q.shape
    page_size = k_pool.shape[1]
    max_pages = pages.shape[1]
    scale = dh ** -0.5
    quant = scales is not None
    in_specs = [
        pl.BlockSpec((1, c, h, dh), lambda s, p, pt, ps_: (s, 0, 0, 0)),
        # THE paged read: the page table entry picks which pool slab
        # this grid step sees — no gathered copy ever materializes
        pl.BlockSpec((1, page_size, h, dh),
                     lambda s, p, pt, ps_: (pt[s, p], 0, 0, 0)),
        pl.BlockSpec((1, page_size, h, dh),
                     lambda s, p, pt, ps_: (pt[s, p], 0, 0, 0)),
    ]
    operands = [pages, pos, q, k_pool, v_pool]
    if quant:
        # per-(page, head) f32 scales [P, H] -> this call's page-table
        # view [S, H, max_pages], one whole (H, max_pages) block per slot
        spec = pl.BlockSpec((1, h, max_pages),
                            lambda s, p, pt, ps_: (s, 0, 0))
        in_specs += [spec, spec]
        operands += [jnp.swapaxes(sc[pages], 1, 2) for sc in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,     # pages + pos steer the index maps
        grid=(s_, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, c, h, dh),
                               lambda s, p, pt, ps_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, c, dh), jnp.float32),      # o accumulator
            pltpu.VMEM((h, c, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((h, c, _LANES), jnp.float32),  # running sum l
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, scale=scale,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, c, h, dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*operands)


def paged_attention(q, k_pool, v_pool, pages, pos,
                    k_scales=None, v_scales=None,
                    interpret: bool | None = None):
    """Fused paged decode attention (module docstring has the contract).

    q [S, C, H, Dh], k/v pool [P, page_size, H, Dh], pages [S, max_pages]
    int32, pos [S] int32 -> [S, C, H, Dh]. With an int8 pool, k_scales /
    v_scales [P, H] f32 per-(page, head) scales must both ride along —
    each slab is dequantized in VMEM right after its DMA."""
    if interpret is None:
        interpret = _auto_interpret()
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    pages = jnp.asarray(pages, jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (q.shape[0],))
    scales = None if k_scales is None else (k_scales, v_scales)
    return _call(q, k_pool, v_pool, pages, pos, scales, bool(interpret))
