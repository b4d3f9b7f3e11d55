"""The adapted projections' backward (llm/lora.py `adapted_dot_general`,
llm/transformer.py `adapted_apply_fn`): the gradients autodiff through
`lora_merge` gives, in rank-r products, with no weight gradient of the
merged kernel formed; a model handed no adapters is the plain one. Tiny
sizes, CPU."""
import itertools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.algorithms.builtin import make_fedavg
from fedml_tpu.config import TrainArgs
from fedml_tpu.llm import federated_lora, transformer
from fedml_tpu.llm.lora import lora_apply_fn, lora_init
from fedml_tpu.llm.moe import MoE
from fedml_tpu.llm.transformer import TransformerLM, adapted_apply_fn
from fedml_tpu.models.hub import mixed_precision_apply
from fedml_tpu.parallel.round import build_round_fn

VOCAB, RANK, ALPHA = 64, 4, 16.0
SHAPES = list(itertools.product(("scan", "unrolled"), (4, 2)))
SHAPE_IDS = [f"{layers}-kv{kv}" for layers, kv in SHAPES]
REMAT = pytest.mark.parametrize("remat", [True, False],
                                ids=["remat", "stored"])
# the parameter paths of the two-layer model: a block's name, its leaves
BLOCKS = {"scan": ["blocks"], "unrolled": ["block_0", "block_1"]}
BLOCK_LEAVES = (
    "RMSNorm_0/scale", "RMSNorm_1/scale", "w_down/kernel", "w_gate/kernel",
    "w_up/kernel", "wk/kernel", "wo/kernel", "wq/kernel", "wv/kernel")


def lm(layers="scan", kv=4, remat=False, **kw):
    return TransformerLM(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_kv_heads=kv, scan_layers=layers == "scan", remat=remat, **kw)


def tokens(seed=1, rows=2, t=24):
    return jax.random.randint(jax.random.key(seed), (rows, t), 0, VOCAB)


def setup(model, targets=("wq", "wk", "wv", "wo")):
    """(base, adapters with both factors drawn, tokens): B starts at zero in
    `lora_init`, which would leave A's gradient zero on both paths."""
    x = tokens()
    base = model.init(jax.random.key(0), x)["params"]
    shapes = lora_init(jax.random.key(2), base, rank=RANK, targets=targets)
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.key(3), len(leaves))
    adapters = jax.tree.unflatten(tree, [
        0.05 * jax.random.normal(k, a.shape) for k, a in zip(keys, leaves)])
    return base, adapters, x


def loss_of(apply, x):
    def loss(adapters):
        out = apply({"params": adapters}, x)
        logits = out[0] if isinstance(out, tuple) else out
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(x, -1, 1)).mean()
    return loss


def paths(model, base, x, dtype="float32", apply=None):
    """The loss through the adapted apply, through autodiff over the merge
    at the same compute dtype, and through the merge in float32."""
    apply = apply or model.apply
    return (
        loss_of(adapted_apply_fn(model, base, ALPHA, dtype, apply), x),
        loss_of(lora_apply_fn(mixed_precision_apply(apply, dtype),
                              base, ALPHA), x),
        loss_of(lora_apply_fn(apply, base, ALPHA), x))


def distance(got, want) -> float:
    """The largest relative distance over the leaves."""
    return max(float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# ------------------------------------------------------------ (a) gradients
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@REMAT
@pytest.mark.parametrize("layers,kv", SHAPES, ids=SHAPE_IDS)
def test_adapter_gradients_equal_autodiff_through_the_merge(
        layers, kv, remat, dtype):
    model = lm(layers, kv, remat)
    base, adapters, x = setup(model)
    new, old, f32 = paths(model, base, x, dtype)
    (l_new, g_new), (l_old, g_old) = (
        jax.value_and_grad(f)(adapters) for f in (new, old))
    assert float(l_new) == float(l_old)     # one forward, the merged one
    assert jax.tree.structure(g_new) == jax.tree.structure(adapters)
    if dtype == "float32":
        assert distance(g_new, g_old) < 1e-5
    else:
        want = jax.grad(f32)(adapters)
        assert distance(g_new, want) <= 1.2 * distance(g_old, want)


def test_adapters_outside_the_blocks_sites_keep_the_merges_gradient():
    # the head's kernel and the expert layer's are no `_dense` site: their
    # adapters are differentiated through the merge, beside the sites'
    model = lm("unrolled", 2, window=8, qk_norm=True,
               moe=MoE(n_experts=8, top_k=2, d_expert=16, held=(0, 4)),
               layer_kinds=(("full", "dense"), ("window", "moe")))
    base, adapters, x = setup(
        model, ("wq", "wo", "w_up", "shared_w_down", "router", "lm_head"))
    assert {"block_0/w_up/kernel", "block_1/moe/shared_w_down/kernel",
            "block_1/moe/router/kernel", "lm_head/kernel"} < set(adapters)
    apply = lambda v, x: model.apply(v, x, mutable=["counters"])
    new, old, _ = paths(model, base, x, apply=apply)
    assert distance(jax.grad(new)(adapters), jax.grad(old)(adapters)) < 1e-5


class StrangerLayer(nn.Module):
    @nn.compact
    def __call__(self, x):
        return x + nn.Dense(32, use_bias=False, name="wo")(
            nn.tanh(nn.Dense(32, use_bias=False, name="wq")(x)))


class Stranger(nn.Module):
    """A module of another make whose kernels lie at a Block's paths."""

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(VOCAB, 32, name="embed")(tokens)
        for i in range(2):
            x = StrangerLayer(name=f"block_{i}")(x)
        return nn.Dense(VOCAB, use_bias=False, name="lm_head")(x)


def test_a_module_that_declares_no_sites_keeps_the_merges_gradient():
    # tests/chipbench/stranger trains such a module through federated_lora:
    # nothing reads the `lora` collection there, so nothing may be withheld
    model = Stranger()
    base, adapters, x = setup(model, ("wq", "wo"))
    assert set(adapters) == {f"block_{i}/{w}/kernel" for i in (0, 1)
                             for w in ("wq", "wo")}
    new, old, _ = paths(model, base, x)
    g_new, g_old = jax.grad(new)(adapters), jax.grad(old)(adapters)
    assert distance(g_new, g_old) < 1e-6
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g_new))


# -------------------------------------------------- (b) no weight gradient
def eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from eqns(sub)


def weight_gradients(loss, adapters, base) -> list:
    """The `dot_general`s of the gradient's jaxpr whose result has the
    shape of an adapted base kernel (a layer's or the stack's, as it lies or
    transposed: autodiff writes x^T dy as (dy^T x)^T) and which contract
    anything but the rank: the merge's own a @ b is no gradient."""
    kernels = set()
    for path in adapters:
        k = base
        for name in path.split("/"):
            k = k[name]
        *stack, din, dout = k.shape
        kernels |= {(*s, *io) for s in ((), tuple(stack))
                    for io in ((din, dout), (dout, din))}
    found = []
    for e in eqns(jax.make_jaxpr(jax.grad(loss))(adapters).jaxpr):
        if e.primitive.name != "dot_general":
            continue
        (lhs_c, _), _ = e.params["dimension_numbers"]
        over = [e.invars[0].aval.shape[i] for i in lhs_c]
        if e.outvars[0].aval.shape in kernels and over != [RANK]:
            found.append((e.outvars[0].aval.shape, over))
    return found


@REMAT
@pytest.mark.parametrize("layers,kv", SHAPES, ids=SHAPE_IDS)
def test_no_product_of_an_adapted_kernels_shape_in_the_backward(
        layers, kv, remat):
    model = lm(layers, kv, remat)
    base, adapters, x = setup(model)
    new, old, _ = paths(model, base, x, "bfloat16")
    assert weight_gradients(new, adapters, base) == []
    # the pin is not vacuous: the merged path forms one per adapted kernel
    assert len(weight_gradients(old, adapters, base)) >= len(adapters)


def test_the_thin_products_run_under_a_scope_of_their_own():
    # `lm.lora` innermost, inside `lm.attn`, on the COMPILED module's
    # op_name paths: what the benchmark's breakdown reads
    model = lm("scan", 2, True)
    base, adapters, x = setup(model)
    new, _, _ = paths(model, base, x, "bfloat16")
    text = jax.jit(jax.grad(new)).lower(adapters).compile().as_text()
    assert re.search(r'op_name="[^"]*lm\.attn/wq/[^"]*lm\.lora/dot_general"',
                     text)


# ------------------------------------------------- (c) one federated round
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layers", ["scan", "unrolled"])
def test_a_federated_round_returns_the_merged_paths_adapters(layers, dtype):
    model = lm(layers, 2, True)
    base, drawn, _ = setup(model)
    t = TrainArgs(epochs=1, batch_size=2, learning_rate=0.5,
                  compute_dtype=dtype)
    alg, adapters = federated_lora(model, base, t, jax.random.key(2),
                                   rank=RANK, alpha=ALPHA)
    # the payload is `lora_init`'s tree: keys, shapes, dtypes
    want = lora_init(jax.random.key(2), base, rank=RANK)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), adapters) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert set(adapters) == {
        f"{b}/{w}/kernel" for w in ("wq", "wk", "wv", "wo")
        for b in BLOCKS[layers]}

    n = 3
    xs = jnp.stack([tokens(10 + i, rows=4) for i in range(n)])
    data = {"x": xs, "y": jnp.roll(xs, -1, 2), "mask": jnp.ones((n, 4))}
    old = make_fedavg(lora_apply_fn(
        mixed_precision_apply(model.apply, dtype), base, ALPHA), t)

    def one_round(algorithm, state):
        out = build_round_fn(algorithm, mesh=None)(
            state, jnp.zeros((n,)), data, jnp.arange(n), jnp.full((n,), 4.0),
            jax.random.key(5), None)
        return out.server_state.params

    copy = lambda tree: jax.tree.map(jnp.array, tree)   # rounds donate
    got = one_round(alg, alg.server_init(copy(drawn), None).replace(
        extra=copy(base)))
    ref = one_round(old, old.server_init(copy(drawn), None))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    moved = jax.tree.map(lambda a, b: a - b, got, drawn)
    moved_ref = jax.tree.map(lambda a, b: a - b, ref, drawn)
    # two bfloat16 backwards of one forward differ by their rounding
    assert distance(moved, moved_ref) < (1e-5 if dtype == "float32" else 0.05)


# ------------------------------------------- (d) without adapters: plain
@REMAT
@pytest.mark.parametrize("layers,kv", SHAPES, ids=SHAPE_IDS)
def test_without_adapters_the_model_is_the_plain_one(
        layers, kv, remat, monkeypatch):
    model = lm(layers, kv, remat)
    x = tokens()
    variables = model.init(jax.random.key(0), x)
    assert set(variables) == {"params"}
    flat = jax.tree_util.tree_flatten_with_path(variables["params"])[0]
    assert sorted("/".join(p.key for p in path) for path, _ in flat) == sorted(
        [f"{b}/{leaf}" for b in BLOCKS[layers] for leaf in BLOCK_LEAVES]
        + ["embed/embedding", "final_norm/scale", "lm_head/kernel"])

    logits = jax.jit(model.apply)(variables, x)
    text = jax.jit(model.apply).lower(variables, x).as_text()
    # the same weights through plain nn.Dense products, bit for bit
    monkeypatch.setattr(
        transformer, "_dense", lambda parent, features, name: nn.Dense(
            features, use_bias=False, name=name))
    np.testing.assert_array_equal(jax.jit(model.apply)(variables, x), logits)
    assert jax.jit(model.apply).lower(variables, x).as_text() == text
