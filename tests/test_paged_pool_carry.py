"""The KV page pool rides the decode layer scan's CARRY, flat and in place
(ISSUE 27; llm/decode.py `make_paged_kv_decode.scan_layers`).

As scan `xs`/`ys` the pool `[L, P, page, H, Dh]` was sliced out per layer
and restacked into a new array: three pool-sized moves a program on the
chip, 32 of a 51 ms decode step (PERF.md section 6, PR 27). Two nets:

- STRUCTURE: in the jaxpr of each of the four programs, with the pool in
  bf16-shaped floats and in int8 with scales, the layer scan has no `xs` or
  `ys` operand of a pool's per-layer shape and carries the flat pool. (The
  compile for a described v5e, whose temporaries must stay far under the
  pool's bytes, sits with the other device-less compiles in
  tests/test_kernels_lower_tpu.py.)
- OFFSET PAGES: with three layers of different content, one padded `chunk`
  then several `step`s, one slot inactive with a stale page-table row, give
  the logits of an oracle that keeps one pool PER LAYER and runs the layers
  in a Python loop; every owned page of the returned pool equals the
  oracle's, every layer's null page (flat page `l * P`) took that layer's
  redirected writes, and no row landed anywhere else.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import decode as dec
from fedml_tpu.llm.quant import (
    lm_head_logits, merged_kernel, project_qkv, rms_norm, split_adapters,
    swiglu_mlp,
)
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.parallel.seq import _NEG

V, D, L, H, FF = 64, 32, 3, 2, 64
DH = D // H
PS, MAX_PAGES, SLOTS, C = 4, 4, 3, 8
P = SLOTS * MAX_PAGES + 1
PROGRAMS = ("chunk", "step", "verify", "chunk_batch")


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    return model.init(jax.random.key(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _cache(quant, fill=0.0):
    z = (L, P, PS, H, DH)
    if not quant:
        return {"k": jnp.full(z, fill, jnp.float32),
                "v": jnp.full(z, fill, jnp.float32)}
    return {"k": jnp.zeros(z, jnp.int8), "v": jnp.zeros(z, jnp.int8),
            "ks": jnp.zeros((L, P, H), jnp.float32),
            "vs": jnp.zeros((L, P, H), jnp.float32)}


def _program_args(name, cache):
    pages = jnp.arange(1, P, dtype=jnp.int32).reshape(SLOTS, MAX_PAGES)
    vec = jnp.zeros((SLOTS,), jnp.int32)
    on = jnp.ones((SLOTS,), bool)
    if name == "chunk":
        return (cache, pages[0], jnp.zeros((1, C), jnp.int32), 0, C - 1)
    if name == "step":
        return (cache, pages, vec, vec, on)
    if name == "verify":
        return (cache, pages, vec, jnp.zeros((SLOTS, 3), jnp.int32), on)
    return (cache, pages, jnp.zeros((SLOTS, C), jnp.int32), vec, vec + C)


def _scans(jaxpr):
    """Every scan equation of a jaxpr, outermost first."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


# ---------------------------------------------------------------- structure
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("name", PROGRAMS)
def test_pool_is_carried_not_scanned(params, name, quant):
    programs = dict(zip(PROGRAMS, dec.make_paged_kv_decode(
        H, PS, kernel=True, quant=quant)))
    cache = _cache(quant)
    jaxpr = jax.make_jaxpr(
        lambda p, *a: programs[name](p, None, *a))(
            params, *_program_args(name, cache)).jaxpr
    scan = next(e for e in _scans(jaxpr) if e.params["length"] == L)
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carried = [v.aval.shape for v in scan.invars[n_consts:n_consts + n_carry]]
    xs = [v.aval.shape[1:] for v in scan.invars[n_consts + n_carry:]]
    ys = [v.aval.shape[1:] for v in scan.outvars[n_carry:]]
    per_layer = {leaf.shape[1:] for leaf in cache.values()}
    assert not per_layer & set(xs), f"pool sliced in as xs: {xs}"
    assert not per_layer & set(ys), f"pool restacked as ys: {ys}"
    for leaf in cache.values():
        flat = (L * P,) + leaf.shape[2:]
        assert carried.count(flat) >= 2, (flat, carried)   # K and V


# ------------------------------------------------------------- offset pages
def _oracle(quant):
    """The parent's layer body, unrolled: a list of L per-layer pools
    `[P, page, H, Dh]`, page ids as the page table gives them, gather
    attention. Works for chunk (B = 1, one row) and step (B = S, C = 1)."""
    def run(params, pools, pages, tokens, posr, wpage, woff):
        _, top_ads, rank_scale = split_adapters(None, 16.0)
        dtype = jnp.float32
        x = params["embed"]["embedding"][tokens]              # [B, C, D]
        out = []
        for l in range(L):
            bl = jax.tree.map(lambda a: a[l], params["blocks"])
            pool = pools[l]
            h = rms_norm(x, bl["RMSNorm_0"]["scale"], 1e-6)
            q, k, v = project_qkv(bl, None, rank_scale, h, H, dtype)
            q, k = dec._rope_rows(q, posr), dec._rope_rows(k, posr)
            if quant:
                pk, ks = dec._kv_quant_write(pool["k"], pool["ks"],
                                             wpage, woff, k)
                pv, vs = dec._kv_quant_write(pool["v"], pool["vs"],
                                             wpage, woff, v)
                pool = {"k": pk, "v": pv, "ks": ks, "vs": vs}
                kk = pk[pages].astype(dtype) * ks[pages][..., None, :, None]
                vv = pv[pages].astype(dtype) * vs[pages][..., None, :, None]
            else:
                pool = {"k": pool["k"].at[wpage, woff].set(k),
                        "v": pool["v"].at[wpage, woff].set(v)}
                kk, vv = pool["k"][pages], pool["v"][pages]
            out.append(pool)
            b = tokens.shape[0]
            kk = kk.reshape((b, -1, H, DH))
            vv = vv.reshape((b, -1, H, DH))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * DH ** -0.5
            live = jnp.arange(kk.shape[1])[None, None, :] <= posr[:, :, None]
            s = jnp.where(live[:, None], s, _NEG)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
            x = x + o.reshape(x.shape[:2] + (-1,)) @ merged_kernel(
                bl, None, "wo", rank_scale, dtype)
            x = swiglu_mlp(bl, None, rank_scale, x, dtype, 1e-6)
        return out, lm_head_logits(params, top_ads, rank_scale, x, dtype,
                                   1e-6)
    return jax.jit(run)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_offset_pages_match_per_layer_oracle(params, kernel, quant):
    chunk, step, _verify, _cb = dec.make_paged_kv_decode(
        H, PS, kernel=kernel, quant=quant)
    chunk, step = jax.jit(chunk), jax.jit(step)
    oracle = _oracle(quant)
    rs = np.random.RandomState(3)
    sentinel = 7.0
    cache = _cache(quant, fill=sentinel)
    pools = [{n: a[l] for n, a in cache.items()} for l in range(L)]
    # slots 0 and 1 own pages 1-4 and 5-8; slot 2 is INACTIVE and its stale
    # row points at slot 0's pages; pages 9.. belong to nobody
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [1, 2, 3, 4]], jnp.int32)
    lens = (6, 5)                              # both padded to C = 8
    toks = rs.randint(1, V, (2, C)).astype(np.int32)
    for slot, n in enumerate(lens):            # one padded chunk per slot
        row, j = table[slot], jnp.arange(C)
        cache, got = chunk(params, None, cache, row, toks[slot:slot + 1],
                           0, n)
        wpage = jnp.where(j < n, row[j // PS], 0)
        pools, want = oracle(params, pools, row[None], toks[slot:slot + 1],
                             j[None], wpage[None], (j % PS)[None])
        np.testing.assert_allclose(got, want[:, n - 1], rtol=2e-5, atol=2e-5)
    pos = np.asarray(lens + (3,), np.int32)
    active = jnp.asarray([True, True, False])
    for _ in range(4):                         # crosses a page boundary
        tok = rs.randint(1, V, SLOTS).astype(np.int32)
        cache, got = step(params, None, cache, table, pos, tok, active)
        wpage = jnp.where(active, table[jnp.arange(SLOTS), pos // PS], 0)
        pools, want = oracle(params, pools, table, tok[:, None],
                             jnp.asarray(pos)[:, None], wpage[:, None],
                             jnp.asarray(pos % PS)[:, None])
        np.testing.assert_allclose(got[:2], want[:2, 0], rtol=2e-4, atol=2e-4)
        pos = pos + np.asarray([1, 1, 0], np.int32)
    for name, leaf in cache.items():
        assert leaf.shape == _cache(quant)[name].shape     # engine's layout
        for l in range(L):
            # owned pages: the oracle's, layer by layer, to rounding (one
            # int8 step) — a row that landed in another layer's pages
            # would differ in both by the size of the values themselves
            np.testing.assert_allclose(
                np.asarray(leaf[l, 1:9], np.float32),
                np.asarray(pools[l][name][1:9], np.float32), rtol=2e-4,
                atol=1 if leaf.dtype == jnp.int8 else 2e-4,
                err_msg=f"{name} layer {l}")
    fresh = _cache(quant, fill=sentinel)
    for l in range(L):
        # nobody's pages are untouched; the layer's null page took the
        # padded tail of both chunks and the inactive slot's steps
        np.testing.assert_array_equal(cache["k"][l, 9:], fresh["k"][l, 9:])
        np.testing.assert_array_equal(cache["v"][l, 9:], fresh["v"][l, 9:])
        assert not np.array_equal(cache["k"][l, 0], fresh["k"][l, 0]), l
        assert not np.array_equal(cache["v"][l, 0], fresh["v"][l, 0]), l
