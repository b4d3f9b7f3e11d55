"""Pallas paged-attention decode kernel — fused attention over the paged
KV pool, reading each slot's pages IN PLACE.

The gather-path paged step (llm/decode.py make_paged_kv_decode) first
materializes every slot's pages into a virtually-contiguous
[S, max_pages * page_size, H, Dh] sequence with an XLA gather, then runs
dense masked attention over it — per decode token that is one full copy
of each slot's context through HBM before a single FLOP of attention.
This kernel removes the copy: the pool stays in HBM (`pl.ANY`), the
device-side page table rides in as a SCALAR-PREFETCH operand, and the
kernel itself DMAs the (page_size, H, Dh) K and V slabs the table names
straight from the pool, a block of pages at a time, while a flash-style
online softmax (running max m, running sum l, o accumulator — the
ops/flash_attention.py recurrence) folds each block in as it streams.
Per-token attention HBM traffic drops from O(context copied + context
read) to O(context read), and the transient gather buffer disappears from
the memory high-water mark.

Shape contract (one transformer layer's pages; the decode layer scan
carries the whole pool flat as `[L * P, page_size, H, Dh]` and calls this
per layer with page ids offset by `l * P`, so "the pool" here is every
layer's and the page table picks this layer's slabs out of it in place):

    q      [S, C, H, Dh]   C queries per slot at global positions
                           pos[s] .. pos[s] + C - 1 (C == 1 is the plain
                           decode step; C > 1 is speculative verify, or a
                           block-diffusion model's window)
    k/v    [P, page_size, KV, Dh]  the persistent page pool; KV divides H
                           (query head i reads KV head i // (H / KV))
    pages  [S, max_pages] int32    page table rows (engine convention:
                           entries beyond a slot's reservation are 0,
                           the reserved null/trash page)
    pos    [S] int32       first query position per slot
    active [S] bool        slots whose rows are wanted (default: all)
    causal (static)        True: query i attends positions <= pos + i;
                           False: every query of the window attends every
                           position < pos + C, the window's own keys both
                           ways (a diffusion block over the blocks before
                           it: llm/decode.py `window`)
    window (static)        W > 0: a window layer's RING (llm/decode.py
                           `make_paged_kv_decode`): `pages` [S, R] is each
                           slot's ring, virtual page p of the slot at ring
                           entry p % R, and query i attends the positions
                           pos + i - W + 1 .. pos + i alone; R must hold
                           the pages from the first query's window to the
                           last query (ceil((W + C) / page_size) + 1 do),
                           and each row must be CONSECUTIVE pool pages (as
                           `ring_pages` lays them out): a block of the walk
                           that stays inside the ring is read as one range.
                           The kernel then runs as `paged_attention_window`
    ->     [S, C, H, Dh]

Grouped heads: the H / KV query heads that share a KV head go through the
MXU TOGETHER against that head's slab: outside the kernel q is laid out
`[S, (H / KV) * C, KV, Dh]` (rows ordered group-major), so one DMA of K and
V a page serves the whole group and a score tile has `(H / KV) * C` rows
where the dense block's has C. With KV == H nothing moves and the program
is the dense block's. (A ring is read otherwise: `_window_kernel`.)

With an int8 pool (`kv_quant: int8`), the per-(page, head) f32 scales
[P, H] are gathered through the page table OUTSIDE the kernel into
[S, H, max_pages] (S * max_pages * H floats — noise next to one slab)
and ride as two further operands blocked per slot: a (1, H) row of the
[P, H] array is not a legal TPU block (the last two block dims must be
(8, 128)-tiled or full), a whole (H, max_pages) slot view is. Each loop
iteration picks its pages' [H, 1] columns with a lane mask — heads already
on sublanes, which is where the (page_size, H, Dh) slab wants them — and
dequantizes the int8 slabs in VMEM: the pool crosses HBM at one byte per
element, which is the whole point.

Semantics match the gather path exactly: query i of slot s attends
virtual positions <= pos[s] + i of the slot's page-table view (the
active-mask write redirect and the null-page-0 convention live in the
caller — writes land before attention, and positions past `pos` are
masked here, so null-page garbage is never read into a live result).

Grid: (S,), one step a slot, and inside it a loop over page BLOCKS whose
trip count is the slot's own: ceil((pos + C) / page_size) pages hold a
position some query attends (with a window, the pages from the one that
holds pos - W + 1 to the one that holds pos + C - 1, the walk starting at
the first), rounded up to `_BLOCK_PAGES` (a ring's walk: to
`_WINDOW_BLOCK_PAGES`), and 0 for a
slot that is not `active` (its stale `pos` and table row are never
looked at; its output row is zeros, which the engine discards). Each
iteration waits for its block's slabs in one of two VMEM buffers (one
DMA semaphore a buffer, K and V apart) after starting the next block's
into the other, so the walk costs what is LIVE — pages past a slot's
last query are neither copied nor computed, where a grid over
(S, max_pages) paid a grid step for every table entry (PERF.md section
6, PR 31). A block's tail past the live pages re-reads table entries
the mask then discards. A page table block is `block` DMAs of K and `block`
of V, one a page; a ring block is ONE of each over its consecutive pages,
but for the one block of a walk that runs past the ring's last page, which
wraps page by page into the same buffer (a wait is by byte count, so one
descriptor of the whole buffer waits for either). The (m, l, o) state is
the loop's carry.
Scores/accumulation are f32; matmuls run in the input dtype with f32
accumulation (bf16 pools keep full MXU rate).

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
# Pages a block: one loop iteration DMAs this many (page_size, H, Dh) K
# and V slabs and folds them into the softmax as ONE [block * page_size]
# key tile. Fixed from the chip's three-fill table (PERF.md section 6,
# PR 31); tables shorter than a block take one block of the whole table.
_BLOCK_PAGES = 8
# Ring pages a block of the window walk (`_window_kernel`), fixed from the
# chip's table at 8 / 16 / 32 / 64 (PERF.md section 6); a ring
# shorter than a block takes one block of the whole ring.
_WINDOW_BLOCK_PAGES = 32


def window_block_pages(ring: int) -> int:
    """Pages a block of the window kernel's walk over a ring of `ring`."""
    return min(_WINDOW_BLOCK_PAGES, ring)


def ring_walk(first: int, queries: int, window: int, page_size: int,
              ring: int) -> tuple[int, int]:
    """(blocks, split blocks) of the window kernel's walk for `queries`
    queries from position `first` over a ring of `ring` pages: host
    arithmetic on positions, the kernel's own. A split block runs past the
    ring's last page and is copied page by page; every other block is one
    K and one V DMA."""
    block = window_block_pages(ring)
    lo = max(first - (window - 1), 0) // page_size
    n = -(-((first + queries - 1) // page_size - lo + 1) // block)
    return n, sum((lo + b * block) % ring + block > ring for b in range(n))


def _dot(a, b, contract, batch):
    """Per-head MXU dot with f32 accumulation (HIGHEST only for f32
    operands — same contract as ops/flash_attention._dot)."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (contract, batch),
        preferred_element_type=jnp.float32, precision=prec)


def _kernel(pages_ref, live_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest,
            block: int, scale: float, quant: bool, queries: int,
            causal: bool):
    if quant:
        # int8 pool: this slot's per-(head, page) scales [1, H, max_pages],
        # page-table-gathered by the caller
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems = rest
    else:
        o_ref, k_buf, v_buf, sems = rest
        ks_ref = vs_ref = None
    s_idx = pl.program_id(0)
    pos = pos_ref[s_idx]
    max_pages = pages_ref.shape[1]
    page_size, h, dh = k_buf.shape[2:]
    c = q_ref.shape[1]
    t_blk = block * page_size
    # THE bound of the walk: blocks that hold a live position of THIS slot
    # (0 for a retired slot — no DMA, no MXU work, a zero output row)
    n_blocks = pl.cdiv(live_ref[s_idx], block)

    def copies(b, buf):
        """The block's 2 * `block` slab DMAs, pool -> VMEM buffer `buf`:
        the page table entry picks each slab straight out of the pool. A
        block's tail past the table re-reads the last entry (any real page
        is finite; its positions are masked below)."""
        out = []
        for i in range(block):
            page = pages_ref[s_idx, jnp.minimum(b * block + i, max_pages - 1)]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[buf, i], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[buf, i], sems.at[1, buf]))
        return out

    @pl.when(n_blocks > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()

    q = q_ref[0]                                       # [C, H, Dh]

    def body(b, carry):
        m, l, o = carry
        buf = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():                                   # double buffering
            for cp in copies(b + 1, 1 - buf):
                cp.start()

        for cp in copies(b, buf):
            cp.wait()
        kb = k_buf[buf]                                # [block, ps, H, Dh]
        vb = v_buf[buf]
        if quant:
            # in-place dequant of the DMA'd slabs: the pool stays int8 in
            # HBM and on the wire; f32 rows exist only in VMEM, cast to
            # the query dtype so the MXU contract matches the bf16 path.
            # Each page's scale column comes out of the slot's
            # [H, max_pages] view by lane mask (a dynamic lane index is
            # not a Mosaic load; one nonzero term keeps the sum exact).
            lane = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)

            def column(ref, i):
                return jnp.where(lane == b * block + i, ref[0], 0.0).sum(
                    axis=1, keepdims=True)[None, None]  # [1, 1, H, 1]

            def dequant(slabs, ref):
                return jnp.concatenate([
                    (slabs[i:i + 1].astype(jnp.float32)
                     * column(ref, i)).astype(q.dtype)
                    for i in range(block)])

            kb, vb = dequant(kb, ks_ref), dequant(vb, vs_ref)
        kb = kb.reshape(t_blk, h, dh)
        vb = vb.reshape(t_blk, h, dh)
        # scores per head: batch H, contract Dh -> [H, C, block * ps]
        s = _dot(q, kb, ((2,), (2,)), ((1,), (1,))) * scale
        # `c` rows hold `queries` positions, once a grouped head
        row = jax.lax.broadcasted_iota(jnp.int32, (1, c, 1), 1)
        qpos = pos + (row if c == queries else row % queries)
        vpos = b * t_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, t_blk), 2)
        seen = vpos <= qpos if causal else vpos < pos + queries
        seen = seen & (vpos < max_pages * page_size)
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))   # [H, C, 1]
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        # [H, C, T] x [T, H, Dh]: batch H, contract T -> [H, C, Dh]
        o_new = o * corr + _dot(
            p.astype(vb.dtype), vb, ((2,), (0,)), ((0,), (1,)))
        return m_new, l_new, o_new

    _, l, o = jax.lax.fori_loop(0, n_blocks, body, (
        jnp.full((h, c, 1), _NEG, jnp.float32),        # running max m
        jnp.zeros((h, c, 1), jnp.float32),             # running sum l
        jnp.zeros((h, c, dh), jnp.float32)))           # o accumulator
    o_ref[0] = jnp.moveaxis(o / jnp.maximum(l, 1e-30), 0, 1).astype(
        o_ref.dtype)


def _window_kernel(pages_ref, live_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, *, block: int, page_size: int,
                   heads: int, scale: float, window: int):
    """`paged_attention_window`: one slot's walk over its ring, from the
    page of the first query's first key. The pool rides in as
    `[P, page_size * KV, Dh]` (a page's rows position-major, KV head minor:
    a bitcast of the pool) and q as `[C * H, Dh]` (query c's head h at row
    c * H + h), so ONE product of every query row with every key row of a
    block scores all heads at once, each row keeping the keys of its own KV
    head (key row r is KV head r % KV) and no operand changing its layout in
    VMEM (PERF.md section 6)."""
    s_idx = pl.program_id(0)
    pos = pos_ref[s_idx]
    ring = pages_ref.shape[1]
    rows, (kv_rows, dh) = q_ref.shape[1], k_buf.shape[2:]
    kv = kv_rows // page_size
    t_rows = block * kv_rows
    n_blocks = pl.cdiv(live_ref[s_idx], block)
    lo = jnp.maximum(pos - (window - 1), 0) // page_size
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def whole(buf, first=0):
        """K and V copies of `block` consecutive pool pages from `first`
        into buffer `buf`: ONE DMA each, and the byte count every wait for
        the buffer is made by, however its pages were started."""
        return [pltpu.make_async_copy(pool.at[pl.ds(first, block)],
                                      dst.at[buf], sems.at[i, buf])
                for i, (pool, dst) in enumerate(pools)]

    def start(b, buf):
        # a ring's pages are consecutive pool pages (llm/decode.py
        # `ring_pages`), so a block that stays inside the ring is one range
        # of the pool; the one block of a walk that runs past the ring's
        # last page wraps to its first page by page (its tail's positions
        # lie past the last query and are masked below)
        j = (lo + b * block) % ring

        @pl.when(j + block <= ring)
        def _whole():
            for cp in whole(buf, pages_ref[s_idx, j]):
                cp.start()

        @pl.when(j + block > ring)
        def _split():
            for i in range(block):
                page = pages_ref[s_idx, (j + i) % ring]
                for n, (pool, dst) in enumerate(pools):
                    pltpu.make_async_copy(pool.at[page], dst.at[buf, i],
                                          sems.at[n, buf]).start()

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]                                       # [C * H, Dh]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    qpos = pos + row // heads
    col = jax.lax.broadcasted_iota(jnp.int32, (1, t_rows), 1)
    own = (row % heads) // (heads // kv) == col % kv   # [C * H, T]

    def body(b, carry):
        m, l, o = carry
        buf = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():                                   # double buffering
            start(b + 1, 1 - buf)

        for cp in whole(buf):
            cp.wait()
        kb = k_buf[buf].reshape(t_rows, dh)
        vb = v_buf[buf].reshape(t_rows, dh)
        s = _dot(q, kb, ((1,), (1,)), ((), ())) * scale          # [C * H, T]
        kpos = (lo + b * block) * page_size + col // kv
        s = jnp.where(own & (kpos <= qpos) & (kpos > qpos - window), s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        o_new = o * corr + _dot(p.astype(vb.dtype), vb, ((1,), (0,)), ((), ()))
        return m_new, l_new, o_new

    _, l, o = jax.lax.fori_loop(0, n_blocks, body, (
        jnp.full((rows, 1), _NEG, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, dh), jnp.float32)))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _auto_interpret() -> bool:
    return jax.default_backend() not in ("tpu",)


def _window_call(q, k_pool, v_pool, pages, pos, active, window: int,
                 interpret: bool):
    """`paged_attention_window` over each slot's ring (`_window_kernel`)."""
    s_, queries, heads, dh = q.shape
    n_pages, page_size, kv = k_pool.shape[:3]
    ring = pages.shape[1]
    block = window_block_pages(ring)
    # the ring pages from the first query's first key to the last query
    first = jnp.maximum(pos - (window - 1), 0) // page_size
    live = jnp.where(active, (pos + queries - 1) // page_size - first + 1, 0)
    rows = pl.BlockSpec((1, queries * heads, dh), lambda s, *_: (s, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)   # stays in HBM; DMA'd by hand
    buf = pltpu.VMEM((2, block, page_size * kv, dh), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_window_kernel, block=block, page_size=page_size,
                          heads=heads, scale=dh ** -0.5, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,     # ring pages, live page counts, pos
            grid=(s_,),
            in_specs=[rows, pool, pool],
            out_specs=rows,
            scratch_shapes=[buf, buf,                  # K, V double buffers
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((s_, queries * heads, dh), q.dtype),
        interpret=interpret,
        name="paged_attention_window",
    )(pages, live, pos, q.reshape(s_, queries * heads, dh),
      *(p.reshape(n_pages, page_size * kv, dh) for p in (k_pool, v_pool)))
    return out.reshape(s_, queries, heads, dh)


@functools.partial(jax.jit, static_argnames=("interpret", "causal", "window"))
def _call(q, k_pool, v_pool, pages, pos, active, scales, interpret: bool,
          causal: bool = True, window: int = 0):
    if window:
        return _window_call(q, k_pool, v_pool, pages, pos, active, window,
                            interpret)
    s_, queries, heads, dh = q.shape
    page_size, h = k_pool.shape[1:3]
    group = heads // h
    if group > 1:
        # the query heads of one KV head ride the rows axis, group-major
        q = q.reshape(s_, queries, h, group, dh).transpose(
            0, 3, 1, 2, 4).reshape(s_, group * queries, h, dh)
    c = group * queries
    max_pages = pages.shape[1]
    block = min(_BLOCK_PAGES, max_pages)
    quant = scales is not None
    # pages that hold a position some query of the slot attends; a retired
    # slot's stale `pos` counts for nothing
    live = jnp.where(active, jnp.minimum(
        pl.cdiv(pos + queries, page_size), max_pages), 0)
    slot = pl.BlockSpec((1, c, h, dh), lambda s, *_: (s, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)   # stays in HBM; DMA'd by hand
    in_specs = [slot, pool, pool]
    operands = [pages, live, pos, q, k_pool, v_pool]
    if quant:
        # per-(page, head) f32 scales [P, H] -> this call's page-table
        # view [S, H, max_pages], one whole (H, max_pages) block per slot
        spec = pl.BlockSpec((1, h, max_pages), lambda s, *_: (s, 0, 0))
        in_specs += [spec, spec]
        operands += [jnp.swapaxes(sc[pages], 1, 2) for sc in scales]
    buf = pltpu.VMEM((2, block, page_size, h, dh), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,     # pages, live page counts, pos
        grid=(s_,),
        in_specs=in_specs,
        out_specs=slot,
        scratch_shapes=[buf, buf,                      # K, V double buffers
                        pltpu.SemaphoreType.DMA((2, 2))],  # [K/V, buffer]
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, scale=dh ** -0.5,
                          quant=quant, queries=queries, causal=causal),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, c, h, dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*operands)
    if group > 1:
        out = out.reshape(s_, group, queries, h, dh).transpose(
            0, 2, 3, 1, 4).reshape(s_, queries, heads, dh)
    return out


def paged_attention(q, k_pool, v_pool, pages, pos,
                    k_scales=None, v_scales=None, active=None,
                    interpret: bool | None = None, causal: bool = True,
                    window: int = 0):
    """Fused paged decode attention (module docstring has the contract).

    q [S, C, H, Dh], k/v pool [P, page_size, KV, Dh], pages [S, max_pages]
    int32, pos [S] int32 -> [S, C, H, Dh]. With an int8 pool, k_scales /
    v_scales [P, KV] f32 per-(page, head) scales must both ride along —
    each slab is dequantized in VMEM right after its DMA. `active` [S]
    bool (default: every slot) marks the slots whose rows are wanted: a
    slot that is not active costs no page read and returns zeros.
    `causal=False`: the window's queries see each other both ways.
    `window` = W > 0: `pages` is each slot's ring and query i attends the
    last W positions up to pos + i (module docstring)."""
    if interpret is None:
        interpret = _auto_interpret()
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    if window and (k_scales is not None or not causal):
        raise NotImplementedError(
            "a window layer's ring is causal over bfloat16 pages: int8 "
            "ring pages and a both-ways window over a ring are not built")
    n_slots = q.shape[0]
    pages = jnp.asarray(pages, jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (n_slots,))
    active = jnp.broadcast_to(
        jnp.asarray(True if active is None else active, jnp.bool_),
        (n_slots,))
    scales = None if k_scales is None else (k_scales, v_scales)
    return _call(q, k_pool, v_pool, pages, pos, active, scales,
                 bool(interpret), bool(causal),
                 **({"window": int(window)} if window else {}))


# --------------------------------------------------------------------------
# Latent pages: a page holds ONE row a token (no heads axis), the compressed
# key/value `c_kv || k_rope` in one pool leaf and the indexer's key in
# another. Both kernels below walk a slot's LIVE pages as the kernel above
# does (grid over slots, a loop over page blocks whose trip count is the
# slot's own, hand-written double-buffered DMAs straight out of the pool),
# and both speak one BLOCKED layout for what is per (query, key):
# `[S, n_blocks, C, block * page_size]`, block b holding virtual positions
# `b * block * page_size ...`. A block past a slot's live pages is never
# touched: the scores there are whatever the buffer held, and the caller's
# selection reads them as not valid.
_LATENT_BLOCK_PAGES = 16        # pages a block: 256 positions at page 16
_INDEX_QUERIES = 64             # queries a grid step of `index_scores`
_LATENT_QUERIES = 32            # queries a grid step of `latent_attention`
_VMEM_LIMIT = 100 * 2 ** 20


def latent_block_pages(n_pages: int) -> int:
    """Pages a block of the latent kernels' walk, for a table of `n_pages`
    (which must be a multiple of it)."""
    return min(_LATENT_BLOCK_PAGES, n_pages)


def _query_tile(c: int, cap: int) -> int:
    """Queries a grid step: the largest halving of `cap` that divides `c`
    into whole sublane tiles of 8, else all `c` at once (a block's last two
    dims must be (8, 128)-tiled or the array's own)."""
    b = min(cap, c)
    while c % b:
        b //= 2
    return b if b % 8 == 0 or b == c else c


def _page_walk(pages_ref, live_ref, pool, buf, sems, block: int, body, init):
    """`fori_loop` over the page blocks of slot `program_id(0)` that hold a
    live page: block b's `block` slabs are waited for in `buf[b % 2]` after
    block b + 1's were started into the other half. `body(b, slabs,
    carry)` sees the block as `[block, page_size, width]`."""
    s_idx = pl.program_id(0)
    n_pages = pages_ref.shape[1]
    n_blocks = pl.cdiv(live_ref[s_idx], block)

    def copies(b, half):
        return [pltpu.make_async_copy(
            pool.at[pages_ref[s_idx, jnp.minimum(b * block + i, n_pages - 1)]],
            buf.at[half, i], sems.at[half]) for i in range(block)]

    @pl.when(n_blocks > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()

    def step(b, carry):
        half = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            for cp in copies(b + 1, 1 - half):
                cp.start()

        for cp in copies(b, half):
            cp.wait()
        return body(b, buf[half], carry)

    return jax.lax.fori_loop(0, n_blocks, step, init)


def _index_kernel(pages_ref, live_ref, q_ref, w_ref, k_hbm, o_ref, k_buf,
                  sems, *, block: int, heads: int):
    page_size, di = k_buf.shape[2:]
    t_blk = block * page_size
    q = q_ref[0]                                   # [Cb * Hi, Di]
    w = w_ref[0]                                   # [Cb * Hi, 1] f32
    cb = q.shape[0] // heads

    def body(b, slabs, carry):
        kb = slabs.reshape(t_blk, di)
        d = _dot(q, kb, ((1,), (1,)), ((), ()))    # [Cb * Hi, T_blk]
        d = jnp.maximum(d, 0.0) * w
        o_ref[0, b] = d.reshape(cb, heads, t_blk).sum(axis=1)
        return carry

    _page_walk(pages_ref, live_ref, k_hbm, k_buf, sems, block, body, 0)


def _latent_kernel(pages_ref, live_ref, q_ref, bias_ref, kv_hbm, o_ref,
                   kv_buf, sems, *, block: int, heads: int, rank: int):
    page_size, width = kv_buf.shape[2:]
    t_blk = block * page_size
    q = q_ref[0]                                   # [Cb * H, width]
    rows = q.shape[0]
    cb = rows // heads

    def body(b, slabs, carry):
        m, l, o = carry
        kv = slabs.reshape(t_blk, width)
        c_kv = kv[:, :rank]
        s = _dot(q, kv, ((1,), (1,)), ((), ()))             # [rows, T_blk]
        bias = bias_ref[0, b]                               # [Cb, T_blk]
        if cb == 1:
            s = s + bias
        else:
            s = (s.reshape(cb, heads, t_blk)
                 + bias[:, None, :]).reshape(rows, t_blk)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # a key the bias rules out weighs nothing, even while every key a
        # row has seen so far is ruled out (m still at the floor)
        p = jnp.where(s > 0.5 * _NEG, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        o_new = o * corr + _dot(p.astype(c_kv.dtype), c_kv,
                                ((1,), (0,)), ((), ()))
        return m_new, l_new, o_new

    _, l, o = _page_walk(
        pages_ref, live_ref, kv_hbm, kv_buf, sems, block, body,
        (jnp.full((rows, 1), _NEG, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, rank), jnp.float32)))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _latent_call(kernel, name, operands, in_specs, out_spec, out_shape,
                 pool, pages, live, tiles, block, interpret):
    page_size, width = pool.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # pages, live page counts
        grid=(pages.shape[0], tiles),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((2, block, page_size, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
    )(pages, live, *operands, pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_call(q, w, k_pool, pages, live, interpret: bool):
    s_, c, hi, di = q.shape
    block = latent_block_pages(pages.shape[1])
    n_blocks, t_blk = pages.shape[1] // block, block * k_pool.shape[1]
    cb = _query_tile(c, _INDEX_QUERIES)
    rows = pl.BlockSpec((1, cb * hi, di), lambda s, j, *_: (s, j, 0))
    wrow = pl.BlockSpec((1, cb * hi, 1), lambda s, j, *_: (s, j, 0))
    out = pl.BlockSpec((1, n_blocks, cb, t_blk), lambda s, j, *_: (s, 0, j, 0))
    return _latent_call(
        functools.partial(_index_kernel, block=block, heads=hi),
        "index_scores",
        [q.reshape(s_, c * hi, di),
         w.astype(jnp.float32).reshape(s_, c * hi, 1)],
        [rows, wrow], out,
        jax.ShapeDtypeStruct((s_, n_blocks, c, t_blk), jnp.float32),
        k_pool, pages, live, c // cb, block, interpret)


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def _attend_call(q, kv_pool, pages, live, bias, rank: int, interpret: bool):
    s_, c, h, width = q.shape
    block = latent_block_pages(pages.shape[1])
    n_blocks, t_blk = bias.shape[1], bias.shape[3]
    cb = _query_tile(c, _LATENT_QUERIES)
    rows = pl.BlockSpec((1, cb * h, width), lambda s, j, *_: (s, j, 0))
    bia = pl.BlockSpec((1, n_blocks, cb, t_blk), lambda s, j, *_: (s, 0, j, 0))
    out = pl.BlockSpec((1, cb * h, rank), lambda s, j, *_: (s, j, 0))
    o = _latent_call(
        functools.partial(_latent_kernel, block=block, heads=h, rank=rank),
        "latent_attention", [q.reshape(s_, c * h, width), bias],
        [rows, bia], out,
        jax.ShapeDtypeStruct((s_, c * h, rank), q.dtype),
        kv_pool, pages, live, c // cb, block, interpret)
    return o.reshape(s_, c, h, rank)


def _latent_args(pages, live, n_slots):
    pages = jnp.asarray(pages, jnp.int32)
    if pages.shape[1] % latent_block_pages(pages.shape[1]):
        raise ValueError(
            f"a table of {pages.shape[1]} pages is no whole number of "
            f"blocks of {_LATENT_BLOCK_PAGES}")
    return pages, jnp.broadcast_to(jnp.asarray(live, jnp.int32), (n_slots,))


def index_scores(q, w, k_pool, pages, live, interpret: bool | None = None):
    """The indexer's scores of every query against its slot's live pages.

    q [S, C, Hi, Di] index queries, w [S, C, Hi] their heads' weights,
    k_pool [P, page_size, Di] the index keys' pool, pages [S, n_pages]
    int32 (a multiple of `latent_block_pages(n_pages)`), live [S] int32
    the pages of each slot that hold a position some query may see (0: the
    slot costs nothing) -> [S, n_blocks, C, block * page_size] float32,
    `sum_j w_j relu(q_j . k_t)` at block `t // (block * page_size)`.
    Blocks past `live` are not written."""
    pages, live = _latent_args(pages, live, q.shape[0])
    return _index_call(q, w, k_pool, pages, live,
                       _auto_interpret() if interpret is None
                       else bool(interpret))


def latent_attention(q, kv_pool, pages, live, bias, rank: int,
                     interpret: bool | None = None):
    """Attention of C queries x H heads a slot over the ONE row a token its
    latent pages hold, in the absorbed form: q [S, C, H, width] (scaled
    already), kv_pool [P, page_size, width] rows `c_kv || k_rope || 0`
    (`width` is rank + rope rounded up to whole lanes of 128: the chip
    stores a row in whole lanes whatever its shape says, and a page slab is
    copied in whole lanes; q's padding is zeros too, so one product over
    `width` is the score), pages / live as `index_scores`, bias
    [S, n_blocks, C, block * page_size] float32 in the blocked layout: 0
    where query c attends the key, `_NEG` where it does not (not selected,
    not yet written, past the query) -> [S, C, H, rank], the weighted sum
    of the attended `c_kv`. A query that attends nothing among the live
    pages gets zeros."""
    pages, live = _latent_args(pages, live, q.shape[0])
    return _attend_call(q, kv_pool, pages, live, bias, int(rank),
                        _auto_interpret() if interpret is None
                        else bool(interpret))
