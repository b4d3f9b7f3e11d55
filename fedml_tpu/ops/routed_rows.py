"""Pallas row moves of the expert layer: what is moved follows the LIVE
count, read at run time.

The expert layer (llm/moe.py) sorts its (token, slot) pairs by held expert
into a row buffer of the worst-case length `P = tokens x top_k`; the rows
that hold a pair routed here are the first `n_live`, a number only the
router's output decides (about P / 8 in the sparse training cell, a handful
of 128 in a decode step). Written as XLA gathers, each of the four moves
(tokens to buffer and back, forward and backward) walks all P rows. The
kernels here walk `n_live`:

- `rows_out`: `out[r] = scale[r] * x[idx[r]]` for `r < n_live` (tokens to
  buffer rows: dispatch forward, and combine's backward with the routing
  weight of row r's pair as `scale`);
- `rows_back`: `out[n] = sum_j ok[n, j] * w[n, j] * src[idx[n, j]]`, float32
  accumulation in slot order (buffer rows to tokens: combine forward, and
  dispatch's backward with `w = 1`);
- `rows_dots`: `out[n, j] = <dy[n], src[idx[n, j]]>` where `ok`, the routing
  weights' gradient in combine's backward (the same walk as `rows_back`).

Each is the other's transpose: `rows_back` over `idx = inv` undoes `rows_out`
over `idx = order // k`.

A TPU array `[rows, d]` lives in tiles of 8 rows, so ONE row of it is no DMA
(Mosaic refuses a slice that cuts a tile). A row moved by DMA is a row of the
SLAB view `[rows, d / 128, 128]`, whose last two dims are whole tiles: 12 KB
in one piece at d = 6,144. The token side is reshaped to slabs outside the
kernels (XLA, `tokens` rows); the buffer side, which the grouped product
wants as a matrix, changes form inside them, a block of live rows at a time
(`rows_out` gathers slabs and stores matrix blocks; `_to_slabs` turns the
live blocks of a matrix buffer into slabs for `rows_back` to gather from).
`live_map` is the same bound for what is computed row by row on the buffer:
a function over the blocks that hold a live row, and no other.

All three follow ops/paged_attention.py's idiom: indices and the live count
are scalar-prefetched, the gathered array stays in HBM (`pl.ANY`), rows come
by `make_async_copy` into one half of a double buffer while the other half
is consumed. `rows_out` and `live_map` run a grid over row blocks in which
a block past the live count does nothing and re-targets the last live block
(no copy in, none out); `rows_back` runs a grid over token blocks whose
loops run over each token's `ok` pairs, packed to the front of its k places
outside the kernel. On the CPU the same bodies run interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_block, _auto_interpret

# buffer rows a grid step of `rows_out` / `live_map`, and the rows of it
# that change form at once; tokens a grid step of `rows_back`
_ROWS = 512
_CHUNK = 64
_TOKENS = 64
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 2 ** 20)


def _slab(d: int) -> tuple:
    """(pieces, lanes) of one row of width d seen as a slab."""
    lanes = 128 if d % 128 == 0 else d
    return d // lanes, lanes


def rows_block(p: int) -> int:
    """Buffer rows a grid step of `rows_out` walks, for a buffer of p."""
    return _auto_block(p, _ROWS)


def rows_walked(n_live, p: int):
    """Rows `rows_out` walks for `n_live` live rows of a buffer of p: the
    live count rounded up to whole blocks."""
    block = rows_block(p)
    return pl.cdiv(n_live, block) * block


def _last_live_block(i, n_ref, rows: int):
    """The block grid step i works on: i itself while it holds a live row,
    else the last that does (block 0 when none): the pipeline then sees no
    new block, so nothing is copied in or out for a step past the live
    count."""
    return jnp.minimum(i, jnp.maximum(pl.cdiv(n_ref[0], rows) - 1, 0))


def _count(n_live):
    return jnp.reshape(n_live, (1,)).astype(jnp.int32)


def _interpret(interpret: bool | None) -> bool:
    return _auto_interpret() if interpret is None else bool(interpret)


def _wait_rows(count, hbm, buf, half, sem):
    """Wait until `count` row DMAs from `hbm` into `buf[half]` have landed,
    whichever they were: one wait of one row's size a copy. Only then is any
    row read (copies may land in any order, so no single row is known to be
    there before all are)."""
    def body(_, carry):
        pltpu.make_async_copy(hbm.at[0], buf.at[half, 0], sem).wait()
        return carry
    jax.lax.fori_loop(0, count, body, 0)


# ------------------------------------------------------------------ rows out
def _rows_out_kernel(idx_ref, n_ref, x_hbm, *rest, rows: int, chunk: int,
                     scaled: bool):
    if scaled:
        s_ref, o_ref, buf, sems = rest
    else:
        (o_ref, buf, sems), s_ref = rest, None
    i = pl.program_id(0)
    n_live = n_ref[0]
    n_blocks = pl.cdiv(n_live, rows)     # THE bound of the walk
    d = o_ref.shape[1]

    def live(b):
        return jnp.minimum(rows, n_live - b * rows)

    def start(b, half):
        """A DMA for each row of block b that is live."""
        def body(r, carry):
            pltpu.make_async_copy(x_hbm.at[idx_ref[b * rows + r]],
                                  buf.at[half, r], sems.at[half]).start()
            return carry
        jax.lax.fori_loop(0, live(b), body, 0)

    @pl.when((i == 0) & (n_blocks > 0))
    def _first():
        start(0, 0)

    @pl.when(i < n_blocks)
    def _block():
        half = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():                                   # double buffering
            start(i + 1, 1 - half)

        _wait_rows(live(i), x_hbm, buf, half, sems.at[half])
        for c in range(rows // chunk):
            at = pl.ds(c * chunk, chunk)
            # slabs -> matrix rows; float32 (exact) for the scale and for
            # a select Mosaic has in 32 bits only
            val = buf[half, at].reshape(chunk, d).astype(jnp.float32)
            if scaled:
                val = val * s_ref[at, :]
            row = i * rows + c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, 1), 0)
            # the block's tail past the live count holds a stale half
            o_ref[at, :] = jnp.where(row < n_live, val, 0).astype(o_ref.dtype)

    @pl.when((i == 0) & (n_blocks == 0))
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_out(x, idx, n_live, scale, interpret: bool):
    n, d = x.shape
    p = idx.shape[0]
    pieces, lanes = _slab(d)
    rows = rows_block(p)
    chunk = _auto_block(rows, _CHUNK)
    scaled = scale is not None

    def last_live(i, idx_ref, n_ref):
        return _last_live_block(i, n_ref, rows), 0

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [idx, _count(n_live), x.reshape(n, pieces, lanes)]
    if scaled:
        in_specs.append(pl.BlockSpec((rows, 1), last_live))
        operands.append(scale.astype(jnp.float32).reshape(p, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # indices, the live count
        grid=(p // rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, d), last_live),
        scratch_shapes=[pltpu.VMEM((2, rows, pieces, lanes), x.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_rows_out_kernel, rows=rows, chunk=chunk,
                          scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, d), x.dtype),
        interpret=interpret, name="moe_rows_out",
        compiler_params=_PARAMS,
    )(*operands)


def rows_out(x, idx, n_live, scale=None, interpret: bool | None = None):
    """`out[r] = scale[r] * x[idx[r]]` for `r < n_live`.

    x [N, d], idx [P] int32 (rows of x; entries from `n_live` on are not
    read), n_live an int32 scalar, scale [P] float32 or None -> [P, d] in
    x's dtype. Rows from `n_live` to the end of its block of
    `rows_block(P)` are zeros; later blocks are NOT written and hold
    whatever the buffer held (`megablox.gmm` reads no row past the group
    sizes' sum, `rows_back` none that is not `ok`)."""
    return _rows_out(x, jnp.asarray(idx, jnp.int32), n_live, scale,
                     _interpret(interpret))


# ------------------------------------------- a map over the live rows' blocks
def _live_map_kernel(n_ref, *refs, fn, n_in: int, rows: int, chunk: int):
    i = pl.program_id(0)

    @pl.when((i < pl.cdiv(n_ref[0], rows)) | (i == 0))
    def _block():
        def body(c, carry):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            outs = fn(*(ref[at] for ref in refs[:n_in]))
            for ref, out in zip(refs[n_in:], outs):
                ref[at] = out.astype(ref.dtype)
            return carry
        jax.lax.fori_loop(0, rows // chunk, body, 0)


def live_map(fn, n_live, arrays, outs, *, chunk: int = 16, name: str,
             interpret: bool | None = None):
    """`fn` over the row blocks (`rows_block(P)` rows) that hold a live row,
    block 0 always: arrays [P, ...] in, `outs` ((trailing shape, dtype),
    ...) out, `fn` seeing `chunk` rows of each at a time. A block past the
    live count is neither read nor written (it re-targets the last live
    block), and holds whatever the buffer held."""
    p = arrays[0].shape[0]
    rows = rows_block(p)

    def spec(trailing):
        zeros = (0,) * len(trailing)
        return pl.BlockSpec(
            (rows,) + tuple(trailing),
            lambda i, n: (_last_live_block(i, n, rows),) + zeros)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(p // rows,),
        in_specs=[spec(a.shape[1:]) for a in arrays],
        out_specs=[spec(shape) for shape, _ in outs])
    return pl.pallas_call(
        functools.partial(_live_map_kernel, fn=fn, n_in=len(arrays),
                          rows=rows, chunk=_auto_block(rows, chunk)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((p,) + tuple(shape), dtype)
                   for shape, dtype in outs],
        interpret=_interpret(interpret), name=name, compiler_params=_PARAMS,
    )(_count(n_live), *arrays)


def _to_slabs(src, n_live, interpret: bool):
    """src [P, d] -> [P, d / 128, 128] over the live rows' blocks."""
    slab = _slab(src.shape[1])
    return live_map(lambda x: (x.reshape((x.shape[0],) + slab),), n_live,
                    [src], [(slab, src.dtype)], chunk=_CHUNK,
                    name="moe_to_slabs", interpret=interpret)[0]


# ----------------------------------------------------------------- rows back
def _rows_back_kernel(cnt_ref, idx_ref, w_ref, src_hbm, *rest, tokens: int,
                      k: int, dots: bool):
    if dots:
        dy_ref, o_ref, buf, sems = rest
    else:
        (o_ref, buf, sems), dy_ref = rest, None
    i = pl.program_id(0)
    pairs = tokens * k
    slab = buf.shape[2:]

    def each_pair(b, t, do, init):
        """`do(p, carry)` over the live pairs of token t of block b: the
        first `cnt` of its k places (the caller packed them there)."""
        return jax.lax.fori_loop(
            0, cnt_ref[b * tokens + t],
            lambda c, carry: do(t * k + c, carry), init)

    def start(b, half):
        def token(t, carry):
            def go(p, c):
                pltpu.make_async_copy(src_hbm.at[idx_ref[b * pairs + p]],
                                      buf.at[half, p], sems.at[half]).start()
                return c
            return each_pair(b, t, go, carry)
        jax.lax.fori_loop(0, tokens, token, 0)

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    half = i % 2

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():                                       # double buffering
        start(i + 1, 1 - half)

    _wait_rows(jax.lax.fori_loop(
        0, tokens, lambda t, n: n + cnt_ref[i * tokens + t], 0),
        src_hbm, buf, half, sems.at[half])

    def row(p):
        return buf[half, p].astype(jnp.float32)

    def token(t, carry):
        if dots:
            dy = dy_ref[t].astype(jnp.float32)

            def dot(p, c):
                o_ref[pl.ds(p, 1), :] = jnp.sum(dy * row(p), axis=0,
                                                keepdims=True)
                return c
            return each_pair(i, t, dot, carry)
        acc = each_pair(                               # slot order
            i, t, lambda p, acc: acc + w_ref[i * pairs + p] * row(p),
            jnp.zeros(slab, jnp.float32))
        o_ref[t] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tokens, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_back(src, idx, ok, w, dy, n_live, interpret: bool):
    p, d = src.shape
    n, k = idx.shape
    pieces, lanes = _slab(d)
    tokens = _auto_block(n, _TOKENS)
    dots = dy is not None
    # a token's live pairs packed to the front of its k places, slot order
    # kept: the kernel's loops then run over what is live, not over k
    cnt = jnp.sum(ok, axis=1, dtype=jnp.int32)
    place = ok[:, :, None] & (
        (jnp.cumsum(ok, axis=1, dtype=jnp.int32) - 1)[:, :, None]
        == jnp.arange(k, dtype=jnp.int32))             # [n, slot, place]

    def packed(a):
        return jnp.sum(jnp.where(place, a[:, :, None], 0), axis=1).reshape(-1)

    # no weights: ones at every place (the dots read none)
    weights = (jnp.ones((n * k,), jnp.float32) if w is None
               else packed(w.astype(jnp.float32)))
    token_block = pl.BlockSpec((tokens, pieces, lanes),
                               lambda i, *_: (i, 0, 0))
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [cnt, packed(idx.astype(jnp.int32)), weights,
                _to_slabs(src, n_live, interpret)]
    if dots:
        in_specs.append(token_block)
        operands.append(dy.reshape(n, pieces, lanes))
        out_spec = pl.BlockSpec((tokens * k, lanes), lambda i, *_: (i, 0))
        out_shape = jax.ShapeDtypeStruct((n * k, lanes), jnp.float32)
    else:
        out_spec = token_block
        out_shape = jax.ShapeDtypeStruct((n, pieces, lanes), src.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # live pairs a token, their rows, weights
        grid=(n // tokens,),
        in_specs=in_specs, out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((2, tokens * k, pieces, lanes), src.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    out = pl.pallas_call(
        functools.partial(_rows_back_kernel, tokens=tokens, k=k, dots=dots),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name="moe_rows_dots" if dots else "moe_rows_back",
        compiler_params=_PARAMS,
    )(*operands)
    if not dots:
        return out.reshape(n, d)
    # back from places to slots; a place past the live count was not written
    at_place = out.sum(-1).reshape(n, 1, k)
    return jnp.sum(jnp.where(place, at_place, 0.0), axis=2)


def rows_back(src, idx, ok, w, n_live, interpret: bool | None = None):
    """`out[n] = sum_j ok[n, j] * w[n, j] * src[idx[n, j]]`, accumulated in
    float32 in slot order j = 0..k-1 and written once.

    src [P, d] whose first `n_live` rows are live (every `ok` index is below
    it), idx [N, k] int32, ok [N, k] bool, w [N, k] float32 or None for
    ones -> [N, d] in src's dtype. A row of src is read only where `ok`."""
    return _rows_back(src, idx, ok, w, None, n_live, _interpret(interpret))


def rows_dots(src, idx, ok, dy, n_live, interpret: bool | None = None):
    """`out[n, j] = <dy[n], src[idx[n, j]]>` where `ok`, else 0: [N, k]
    float32 (src, idx, ok, n_live as `rows_back`; dy [N, d])."""
    return _rows_back(src, idx, ok, None, dy, n_live, _interpret(interpret))
