"""The two latent-page kernels against dense oracles over the gathered pages,
slot by slot, at ragged fills in ONE call (ops/paged_attention.py
`index_scores`, `latent_attention`).

Both walk only the page blocks that hold a live page of a slot, so what needs
pinning is every place the walk's bound can be off by one (one page, a block's
last page, one past a block, the full table), a retired slot whose stale row
names pages a live slot owns, and a selection that leaves fewer keys than a
block holds, or none in a whole block."""
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.paged_attention import (
    _LATENT_BLOCK_PAGES, _NEG, index_scores, latent_attention,
    latent_block_pages,
)

PAGE, HEADS, RANK, WIDTH, IHEADS, IDIM = 4, 4, 16, 128, 2, 8
N_PAGES = 3 * _LATENT_BLOCK_PAGES            # three blocks a table
T_BLK = _LATENT_BLOCK_PAGES * PAGE
N_VIRT = N_PAGES * PAGE
# name -> first query position; the slot's last query sits at + c - 1
FILLS = {"pos0": 0, "page_end": PAGE - 1, "block_end": T_BLK - 1,
         "block_and_one": T_BLK, "ragged": T_BLK + 3 * PAGE + 1,
         "full_table": N_VIRT - 1}
KINDS = {"bf16": (jnp.bfloat16, 3e-2), "f32": (jnp.float32, 2e-5)}


def _setup(c, dtype, seed=0):
    rs = np.random.RandomState(seed)
    names = list(FILLS)
    s = len(names) + 1                       # the last slot is retired
    pos = np.array([max(0, FILLS[n] - c + 1) for n in names] + [N_VIRT // 2])
    active = np.array([True] * len(names) + [False])
    n_pool = s * N_PAGES + 1
    perm = rs.permutation(np.arange(1, n_pool))
    pages = perm.reshape(s, N_PAGES).astype(np.int32)
    pages[-1] = pages[0]                     # a stale row over a live slot's
    live = np.where(active, -(-(pos + c) // PAGE), 0).astype(np.int32)
    kv = jnp.asarray(rs.randn(n_pool, PAGE, WIDTH), dtype)
    kv = kv.at[..., RANK + 8:].set(0)        # the row's padding is zeros
    ik = jnp.asarray(rs.randn(n_pool, PAGE, IDIM), dtype)
    return rs, s, pos, active, pages, live, kv, ik


def _blocked(a, s, c):
    """[S, C, T] -> the kernels' [S, n_blocks, C, T_blk]."""
    return a.reshape(s, c, -1, T_BLK).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("c", [1, 3])
def test_index_scores_match_the_dense_product_over_live_pages(kind, c):
    dtype, tol = KINDS[kind]
    rs, s, pos, active, pages, live, _kv, ik = _setup(c, dtype)
    q = jnp.asarray(rs.randn(s, c, IHEADS, IDIM), dtype)
    w = jnp.asarray(rs.randn(s, c, IHEADS), jnp.float32)
    got = np.asarray(index_scores(q, w, ik, pages, live))
    assert got.shape == (s, N_PAGES // _LATENT_BLOCK_PAGES, c, T_BLK)
    keys = np.asarray(ik, np.float32)[pages].reshape(s, N_VIRT, IDIM)
    d = np.einsum("schd,std->scht", np.asarray(q, np.float32), keys)
    want = _blocked((np.maximum(d, 0) * np.asarray(w)[..., None]).sum(2), s, c)
    for i in range(s):
        blocks = -(-int(live[i]) // _LATENT_BLOCK_PAGES)
        assert (blocks > 0) == bool(active[i])
        np.testing.assert_allclose(got[i, :blocks], want[i, :blocks],
                                   atol=tol * 10, rtol=tol)


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("c", [1, 3])
def test_latent_attention_matches_masked_attention_over_the_rows(kind, c):
    dtype, tol = KINDS[kind]
    rs, s, pos, active, pages, live, kv, _ik = _setup(c, dtype, seed=1)
    q = jnp.asarray(rs.randn(s, c, HEADS, WIDTH) * 0.3, dtype)
    qpos = pos[:, None] + np.arange(c)
    seen = np.arange(N_VIRT)[None, None, :] <= qpos[:, :, None]
    # a selection: about a third of what a query sees, its own position
    # always, and for one slot nothing in the whole first block
    sel = seen & (rs.rand(s, c, N_VIRT) < 0.35)
    sel[np.arange(s)[:, None], np.arange(c)[None], np.minimum(
        qpos, N_VIRT - 1)] = True
    sel[4, :, :T_BLK] = False
    bias = jnp.asarray(_blocked(np.where(sel, 0.0, _NEG), s, c), jnp.float32)
    got = np.asarray(latent_attention(q, kv, pages, live, bias, RANK),
                     np.float32)
    rows = np.asarray(kv, np.float32)[pages].reshape(s, N_VIRT, WIDTH)
    sc = np.einsum("schd,std->scht", np.asarray(q, np.float32), rows)
    sc = np.where(sel[:, :, None, :], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("scht,std->schd", p, rows[..., :RANK])
    for i in range(s):
        if active[i]:
            np.testing.assert_allclose(got[i], want[i], atol=tol, rtol=tol)
        else:
            assert not got[i].any()          # a retired slot: zeros, no read


def test_fewer_keys_selected_than_the_table_holds_and_a_lone_key():
    """A query whose selection is ONE key returns that key's row whatever
    else its pages hold."""
    rs, s, pos, active, pages, live, kv, _ik = _setup(1, jnp.float32, seed=2)
    q = jnp.asarray(rs.randn(s, 1, HEADS, WIDTH), jnp.float32)
    sel = np.zeros((s, 1, N_VIRT), bool)
    sel[:, 0, 0] = True
    bias = jnp.asarray(_blocked(np.where(sel, 0.0, _NEG), s, 1), jnp.float32)
    got = np.asarray(latent_attention(q, kv, pages, live, bias, RANK))
    first = np.asarray(kv)[pages[:, 0], 0, :RANK]            # [S, RANK]
    for i in range(s - 1):
        np.testing.assert_allclose(got[i, 0], np.broadcast_to(
            first[i], (HEADS, RANK)), atol=1e-6)


def test_a_table_that_is_no_whole_number_of_blocks_is_refused():
    assert latent_block_pages(7) == 7
    assert latent_block_pages(100) == _LATENT_BLOCK_PAGES
    with pytest.raises(ValueError, match="no whole number of blocks"):
        index_scores(jnp.zeros((1, 1, 2, 8)), jnp.zeros((1, 1, 2)),
                     jnp.zeros((4, PAGE, 8)),
                     jnp.zeros((1, _LATENT_BLOCK_PAGES + 1), jnp.int32),
                     jnp.ones((1,), jnp.int32))
