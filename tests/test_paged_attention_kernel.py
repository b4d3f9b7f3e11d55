"""The paged-attention kernel against dense masked attention over the
gathered pages, slot by slot, at ragged fills in ONE call.

The kernel walks only the page blocks that hold a live position of a slot
(ops/paged_attention.py), so what needs pinning is every place the walk's
bound can be off by one: position 0, a last query one short of a page
boundary, on it and one past, a page count that fills a block exactly or
not, the full table, a speculative window that overruns it, and a retired
slot whose stale row names pages a live slot owns. The token-identity pins
of the engine (tests/test_decode_kernel_spec.py) sit downstream of this.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.paged_attention import _BLOCK_PAGES, paged_attention

PAGE, HEADS, DH = 4, 2, 8
MAX_PAGES = 2 * _BLOCK_PAGES + 3         # three blocks, the last one ragged
N_VIRT = MAX_PAGES * PAGE
BLOCK_TOKENS = _BLOCK_PAGES * PAGE


def _fills(c):
    """name -> first query position; the slot's last query sits at +c-1."""
    return {
        "pos0": 0,
        "short_of_page": 3 * PAGE - c - 1,     # last query: offset ps - 2
        "page_end": 3 * PAGE - c,              # last query: a page's last row
        "page_start": 3 * PAGE - c + 1,        # last query: next page's first
        "one_block": BLOCK_TOKENS - c,         # live pages == one block
        "block_and_a_page": BLOCK_TOKENS - c + 1,
        "ragged_blocks": BLOCK_TOKENS + 3 * PAGE + 1,
        "full_table": N_VIRT - c,
        "overrun": N_VIRT - 1,                 # c > 1: queries past the table
    }


KINDS = {"bf16": jnp.bfloat16, "f32": jnp.float32, "int8": jnp.bfloat16}
TOL = {"bf16": 3e-2, "f32": 2e-5, "int8": 3e-2}


def _reference(q, k_pool, v_pool, pages, pos, scales):
    """Dense masked attention over each slot's gathered pages, in float32:
    query i of slot s attends virtual positions <= pos[s] + i."""
    def gathered(pool, sc):
        g = np.asarray(pool, np.float32)[pages]         # [S, MP, ps, H, Dh]
        if sc is not None:
            g = g * np.asarray(sc)[pages][:, :, None, :, None]
            g = np.asarray(jnp.asarray(g).astype(q.dtype), np.float32)
        return g.reshape(pages.shape[0], N_VIRT, HEADS, DH)
    kk = gathered(k_pool, scales and scales[0])
    vv = gathered(v_pool, scales and scales[1])
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float32), kk) * DH ** -0.5
    qpos = np.asarray(pos)[:, None] + np.arange(q.shape[1])
    live = np.arange(N_VIRT)[None, None, :] <= qpos[:, :, None]
    s = np.where(live[:, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), vv)


@functools.lru_cache(maxsize=None)
def _run(c, kind):
    """One kernel call over every fill (+ the retired slot, last), the same
    call without the retired slot, and the reference."""
    fills = _fills(c)
    names = list(fills)
    n = len(names) + 1
    rng = np.random.default_rng(7 * c + len(kind))
    n_pool = 1 + (n - 1) * MAX_PAGES
    ids = 1 + rng.permutation(n_pool - 1).reshape(n - 1, MAX_PAGES)
    pos = np.array(list(fills.values()) + [N_VIRT // 2], np.int32)
    pages = np.zeros((n, MAX_PAGES), np.int32)
    for s, name in enumerate(names):
        held = min(-(-(fills[name] + c) // PAGE), MAX_PAGES)
        pages[s, :held] = ids[s, :held]    # past the reservation: null page 0
    pages[-1] = pages[names.index("ragged_blocks")]       # the stale row
    active = np.array([True] * (n - 1) + [False])
    dtype = KINDS[kind]
    shape = (n_pool, PAGE, HEADS, DH)
    if kind == "int8":
        k_pool, v_pool = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                          for _ in range(2))
        scales = tuple(jnp.asarray(rng.uniform(0.002, 0.02, (n_pool, HEADS)),
                                   jnp.float32) for _ in range(2))
    else:
        k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), dtype)
                          for _ in range(2))
        scales = None
    q = jnp.asarray(rng.normal(size=(n, c, HEADS, DH)), dtype)
    sc = scales or (None, None)
    out = paged_attention(q, k_pool, v_pool, pages, pos, *sc, active=active)
    alone = paged_attention(q[:-1], k_pool, v_pool, pages[:-1], pos[:-1], *sc)
    ref = _reference(q, k_pool, v_pool, pages, pos, scales)
    return names, np.asarray(out, np.float32), np.asarray(alone, np.float32), ref


CASES = [(c, kind) for c in (1, 4) for kind in KINDS]


@pytest.mark.parametrize("fill", list(_fills(1)))
@pytest.mark.parametrize("c,kind", CASES)
def test_live_slot_matches_dense_attention(c, kind, fill):
    names, out, _alone, ref = _run(c, kind)
    s = names.index(fill)
    np.testing.assert_allclose(out[s], ref[s], atol=TOL[kind], rtol=TOL[kind])


@pytest.mark.parametrize("c,kind", CASES)
def test_retired_slot_costs_its_neighbours_nothing(c, kind):
    """The retired slot's stale row names a live slot's pages: every live
    row equals the call without it bit for bit, and its own row is finite
    (the engine discards it: `_decode_tail`)."""
    _names, out, alone, _ref = _run(c, kind)
    np.testing.assert_array_equal(out[:-1], alone)
    assert np.isfinite(out[-1]).all()


def test_retired_row_is_zeros_where_its_twin_is_not():
    """Same table row, same pool: the live twin attends, the retired slot
    was never walked."""
    names, out, _alone, _ref = _run(1, "f32")
    assert np.abs(out[names.index("ragged_blocks")]).max() > 0
    assert np.abs(out[-1]).max() == 0
