"""The expert layer's row moves (ops/routed_rows.py) against their gather
forms, and `_dispatch` / `_combine` (llm/moe.py) against plain jnp through
their gradients: values, `dx`, `dys`, `dw`; at no live row, one, a count that
is no multiple of the row block, and the whole buffer; with everything past
the live count poisoned; under `jax.vmap` with different live counts and
under `jax.checkpoint`. Tiny sizes, CPU, kernels interpreted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import moe
from fedml_tpu.ops import routed_rows as rr

N, K, D = 24, 2, 32
P = N * K
LIVE = [0, 1, 17, P]


def routing(n_live, seed=0):
    """(order, inv [N, K], here [N, K]) with `n_live` pairs routed here, as
    ExpertLayer sorts them: the pairs here first, in pair order."""
    rng = np.random.default_rng(seed)
    here = np.zeros(P, bool)
    here[rng.choice(P, n_live, replace=False)] = True
    order = np.argsort(~here, kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    return (jnp.asarray(order), jnp.asarray(inv.reshape(N, K)),
            jnp.asarray(here.reshape(N, K)))


def draw(seed, *shape):
    return jax.random.normal(jax.random.key(seed), shape)


def rows_mask(n_live):
    return (jnp.arange(P) < n_live)[:, None]


# ---------------------------------------------------------------- the kernels
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("n_live", LIVE)
def test_rows_out_is_the_gather_over_the_live_rows(n_live, scaled):
    order, _, _ = routing(n_live)
    x = draw(1, N, D)
    scale = jax.random.uniform(jax.random.key(2), (P,)) if scaled else None
    out = rr.rows_out(x, order // K, jnp.int32(n_live), scale)
    want = x[order // K] * (1.0 if scale is None else scale[:, None])
    np.testing.assert_allclose(out[:n_live], want[:n_live], rtol=1e-6)
    # the tail of the last block walked is zeros
    walked = int(rr.rows_walked(n_live, P))
    assert walked == -(-n_live // rr.rows_block(P)) * rr.rows_block(P)
    np.testing.assert_array_equal(out[n_live:walked], 0.0)


@pytest.mark.parametrize("n_live", LIVE)
def test_rows_back_is_the_weighted_sum_of_the_rows_that_are_ok(n_live):
    _, inv, here = routing(n_live)
    src = jnp.where(rows_mask(n_live), draw(3, P, D), jnp.nan)   # poison
    w = jax.random.uniform(jax.random.key(4), (N, K))
    clean = jnp.nan_to_num(src)[inv]                             # [N, K, D]
    out = rr.rows_back(src, inv, here, w, jnp.int32(n_live))
    want = jnp.sum(jnp.where(here[..., None], w[..., None] * clean, 0), 1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    ones = rr.rows_back(src, inv, here, None, jnp.int32(n_live))
    np.testing.assert_allclose(
        ones, jnp.sum(jnp.where(here[..., None], clean, 0), 1),
        rtol=1e-5, atol=1e-6)
    dy = draw(5, N, D)
    dots = rr.rows_dots(src, inv, here, dy, jnp.int32(n_live))
    np.testing.assert_allclose(
        dots, jnp.where(here, jnp.einsum("nd,nkd->nk", dy, clean), 0),
        rtol=1e-5, atol=1e-5)


def test_bfloat16_rows_move_exactly_and_sum_in_float32():
    order, inv, here = routing(17)
    x = draw(1, N, D).astype(jnp.bfloat16)
    out = rr.rows_out(x, order // K, jnp.int32(17))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(out[:17], x[order // K][:17])
    w = jax.random.uniform(jax.random.key(4), (N, K))
    back = rr.rows_back(out, inv, here, w, jnp.int32(17))
    want = jnp.einsum("nk,nkd->nd", jnp.where(here, w, 0),
                      out[inv].astype(jnp.float32))
    assert back.dtype == jnp.bfloat16
    np.testing.assert_array_equal(back, want.astype(jnp.bfloat16))


# ---------------------------------------------- the layer's two moves, by vjp
def by_hand(x, w, gain, order, inv, here, n_here):
    """dispatch, a stand-in for the experts (each row times `gain`),
    combine: written with plain gathers."""
    xs = jnp.where(rows_mask(n_here), x[order // K], 0) * gain
    return jnp.einsum("nk,nkd->nd", jnp.where(here, w, 0), xs[inv])


@jax.custom_vjp
def poisoned(rows, n_here):
    """Identity on the live rows; NaN past them, forward and backward."""
    return jnp.where(rows_mask(n_here), rows, jnp.nan)


poisoned.defvjp(
    lambda rows, n: (poisoned(rows, n), n),
    lambda n, g: (jnp.where(rows_mask(n), g, jnp.nan), None))


def program(x, w, gain, order, inv, here, n_here):
    xs = poisoned(moe._dispatch(x, order, inv, here, n_here), n_here)
    ys = poisoned(xs * gain, n_here)
    return moe._combine(ys, w, order, inv, here, n_here)


def loss_and_grads(fn, x, w, gain, *route):
    def loss(x, w, gain):
        return jnp.sum(jnp.sin(fn(x, w, gain, *route)))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, gain)


@pytest.mark.parametrize("n_live", LIVE)
def test_dispatch_and_combine_give_the_gathers_gradients_under_poison(n_live):
    route = (*routing(n_live), jnp.int32(n_live))
    x, gain = draw(1, N, D), draw(2, P, 1)
    w = jax.random.uniform(jax.random.key(3), (N, K))
    got, (dx, dw, dgain) = loss_and_grads(program, x, w, gain, *route)
    want, (dx2, dw2, dgain2) = loss_and_grads(by_hand, x, w, gain, *route)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(dx, dx2, rtol=1e-4, atol=1e-5)    # dx
    np.testing.assert_allclose(dw, dw2, rtol=1e-4, atol=1e-5)    # dw
    # dys reaches `gain` through the live rows only
    np.testing.assert_allclose(dgain[:n_live], dgain2[:n_live],
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(np.asarray(dx)).all()
    assert np.isfinite(np.asarray(dw)).all()


@pytest.mark.parametrize("lives", [(17,), (5, 40)], ids=["one", "two"])
def test_vmapped_clients_each_walk_their_own_live_count(lives):
    routes = [(*routing(n, seed=n), jnp.int32(n)) for n in lives]
    stacked = [jnp.stack(a) for a in zip(*routes)]
    b = len(lives)
    x, gain = draw(1, b, N, D), draw(2, b, P, 1)
    w = jax.random.uniform(jax.random.key(3), (b, N, K))

    def one(fn):
        return lambda x, w, gain, *route: loss_and_grads(
            fn, x, w, gain, *route)

    got = jax.vmap(one(program))(x, w, gain, *stacked)
    for i, route in enumerate(routes):
        want = one(by_hand)(x[i], w[i], gain[i], *route)
        np.testing.assert_allclose(got[0][i], want[0], rtol=1e-5)
        np.testing.assert_allclose(got[1][0][i], want[1][0],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got[1][1][i], want[1][1],
                                   rtol=1e-4, atol=1e-5)


def test_the_moves_run_again_under_checkpoint():
    route = (*routing(17), jnp.int32(17))
    x, gain = draw(1, N, D), draw(2, P, 1)
    w = jax.random.uniform(jax.random.key(3), (N, K))
    remat = jax.checkpoint(program)
    got, grads = jax.jit(lambda *a: loss_and_grads(remat, *a))(
        x, w, gain, *route)
    want, grads2 = loss_and_grads(by_hand, x, w, gain, *route)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(grads[:2], grads2[:2]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n_live", LIVE)
def test_the_products_neighbours_compute_the_live_rows_only(n_live):
    """`_swiglu` and `_twice`'s summed cotangent against jnp on the live
    rows, everything past them poisoned on the way in and on the way back."""
    n = jnp.int32(n_live)
    gate, up = draw(1, P, D), draw(2, P, D)

    def program(gate, up):
        a, b = moe._twice(poisoned(gate, n), n)
        return poisoned(moe._swiglu(a, 2.0 * b, n) + moe._swiglu(b, up, n), n)

    def by_hand(gate, up):
        return jax.nn.silu(gate) * 2.0 * gate + jax.nn.silu(gate) * up

    def loss(fn):
        return lambda g, u: jnp.sum(jnp.where(rows_mask(n_live),
                                              jnp.sin(fn(g, u)), 0))

    got, grads = jax.value_and_grad(loss(program), (0, 1))(gate, up)
    want, grads2 = jax.value_and_grad(loss(by_hand), (0, 1))(gate, up)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(grads, grads2):
        np.testing.assert_allclose(a[:n_live], b[:n_live],
                                   rtol=1e-4, atol=1e-5)
        assert np.isfinite(np.asarray(a[:n_live])).all()


@pytest.mark.parametrize("picked,walked", [((1, 2), 2 * 16 * 2), ((5, 6), 0)],
                         ids=["every_token_here", "no_token_here"])
def test_the_layer_counts_the_rows_it_walked(picked, walked):
    spec = moe.MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4), scale=2.5)
    h = draw(4, 2, 16, 32)
    p = moe.ExpertLayer(spec).init(jax.random.key(3), h)["params"]
    # the selection bias sends EVERY token to the two experts picked
    p["e_score_correction_bias"] = jnp.zeros(8).at[
        jnp.array(picked)].set(9.0)
    out, sown = moe.ExpertLayer(spec).apply({"params": p}, h,
                                            mutable=["counters"])
    assert np.isfinite(np.asarray(out)).all()
    assert int(sown["counters"]["moe_rows_walked"][0]) == walked
    folded = moe.fold_counters(sown["counters"])
    assert float(folded["moe_rows_walked"]) == walked
