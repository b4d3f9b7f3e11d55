"""FedLLM slice (BASELINE.md workload 5): transformer + LoRA + sequence
parallelism. Ring/Ulysses attention must equal dense causal attention;
federated LoRA must train adapters only; the (silos, seq) round must match
the flat engine exactly (same batching, same rngs)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.config import TrainArgs
from fedml_tpu.llm import (
    TransformerLM, count_params, federated_lora, lora_apply_fn, lora_init,
    lora_merge, make_fedllm_seq_round, shard_fedllm_data,
)
from fedml_tpu.core.algorithm import ServerState
from fedml_tpu.ops import tree as tu
from fedml_tpu.parallel.mesh import make_mesh
from fedml_tpu.parallel.round import build_round_fn
from fedml_tpu.parallel.seq import (
    dense_causal_attention, ring_attention, ulysses_attention,
)

VOCAB = 32


def _qkv(seed, b=2, t=32, h=4, d=8):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(b, t, h, d).astype(np.float32))
                 for _ in range(3))


def _seq_mesh(n, name="seq"):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def test_ring_attention_matches_dense():
    q, k, v = _qkv(0)
    ref = dense_causal_attention(q, k, v)
    mesh = _seq_mesh(8)
    f = shard_map(
        functools.partial(ring_attention, axis_name="seq"),
        mesh=mesh, in_specs=P(None, "seq"), out_specs=P(None, "seq"))
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_dense():
    q, k, v = _qkv(1)
    ref = dense_causal_attention(q, k, v)
    mesh = _seq_mesh(4)
    f = shard_map(
        functools.partial(ulysses_attention, axis_name="seq"),
        mesh=mesh, in_specs=P(None, "seq"), out_specs=P(None, "seq"))
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_grads_match_dense():
    q, k, v = _qkv(2, t=16)
    mesh = _seq_mesh(4)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="seq"),
        mesh=mesh, in_specs=P(None, "seq"), out_specs=P(None, "seq"))
    g_ref = jax.grad(lambda *a: dense_causal_attention(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda *a: ring(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


def _tiny_lm(**kw):
    cfg = dict(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, d_ff=64)
    cfg.update(kw)
    return TransformerLM(**cfg)


def test_transformer_causality():
    model = _tiny_lm()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    toks = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (1, 8)))
    logits = model.apply({"params": params}, toks)
    toks2 = toks.at[0, 5].set((toks[0, 5] + 3) % VOCAB)
    logits2 = model.apply({"params": params}, toks2)
    # positions < 5 see no difference; position >= 5 does
    np.testing.assert_allclose(np.asarray(logits[0, :5]),
                               np.asarray(logits2[0, :5]), atol=1e-5)
    assert float(jnp.abs(logits[0, 5:] - logits2[0, 5:]).max()) > 1e-4


def test_lora_zero_init_is_identity_and_counts():
    model = _tiny_lm()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    adapters = lora_init(jax.random.key(1), params, rank=4)
    merged = lora_merge(params, adapters)
    toks = jnp.zeros((2, 8), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": merged}, toks)),
        np.asarray(model.apply({"params": params}, toks)), atol=1e-6)
    # adapters are a small fraction of the base
    assert count_params(adapters) < 0.25 * count_params(params)


def _lm_task(n_clients=4, s=8, t=16, seed=0):
    """Learnable toy LM: next token = (token + 1) mod VOCAB."""
    rs = np.random.RandomState(seed)
    starts = rs.randint(0, VOCAB, (n_clients, s, 1))
    seqs = (starts + np.arange(t + 1)) % VOCAB
    return {
        "x": seqs[:, :, :-1].astype(np.int32),
        "y": seqs[:, :, 1:].astype(np.int32),
        "mask": np.ones((n_clients, s), np.float32),
    }


def test_federated_lora_flat_trains_adapters_only():
    model = _tiny_lm()
    base = model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]
    t = TrainArgs(epochs=1, batch_size=4, learning_rate=0.5)
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=4)
    data = _lm_task()
    n = data["x"].shape[0]
    round_fn = build_round_fn(alg, mesh=None)
    st = alg.server_init(adapters, None)
    ids = jnp.arange(n)
    weights = jnp.full((n,), 8.0)
    losses = []
    for r in range(8):
        out = round_fn(st, jnp.zeros((n,)),
                       {k: jnp.asarray(v) for k, v in data.items()},
                       ids, weights, jax.random.fold_in(jax.random.key(2), r),
                       None)
        st = out.server_state
        losses.append(float(out.metrics["train_loss"]))
    assert losses[-1] < losses[0] * 0.8, losses
    # the trained state is adapters-shaped, not base-shaped
    assert set(st.params.keys()) == set(
        lora_init(jax.random.key(1), st.extra, rank=4).keys())
    # the frozen base rode the server state as a program ARGUMENT (never
    # a closure literal) and came back untouched
    assert jax.tree.structure(st.extra) == jax.tree.structure(base)


@pytest.mark.slow
def test_fedllm_seq_round_matches_flat():
    """(silos=2, seq=4) ring-attention round == flat engine round, exactly:
    same rngs, same batch composition, sum-CE/psum == batch-mean grads."""
    model = _tiny_lm()
    base = model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]
    t = TrainArgs(epochs=1, batch_size=8, learning_rate=0.5)
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=4)
    data = _lm_task(n_clients=2)
    n = data["x"].shape[0]
    ids = jnp.arange(n)
    weights = jnp.full((n,), 8.0)
    rng = jax.random.key(7)

    flat_round = build_round_fn(alg, mesh=None)
    st_flat = alg.server_init(jax.tree.map(jnp.array, adapters), None)
    flat_out = flat_round(st_flat, jnp.zeros((n,)),
                          {k: jnp.asarray(v) for k, v in data.items()},
                          ids, weights, rng, None)

    # the flat round consumed (donated) `base` with its server state
    base = flat_out.server_state.extra
    mesh = make_mesh({"silos": 2, "seq": 4})
    seq_round = make_fedllm_seq_round(model, base, t, mesh)
    st_seq = ServerState(jax.tree.map(jnp.array, adapters), None,
                         jnp.int32(0), None)
    hdata = shard_fedllm_data(data, mesh)
    new_st, metrics = seq_round(st_seq, base, hdata, ids, weights, rng)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        flat_out.server_state.params, new_st.params)


def test_fedllm_seq_round_converges():
    model = _tiny_lm()
    base = model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]
    t = TrainArgs(epochs=1, batch_size=4, learning_rate=0.5)
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=4)
    data = _lm_task(n_clients=2)
    mesh = make_mesh({"silos": 2, "seq": 4})
    seq_round = make_fedllm_seq_round(model, base, t, mesh)
    st = ServerState(jax.tree.map(jnp.array, adapters), None, jnp.int32(0), None)
    hdata = shard_fedllm_data(data, mesh)
    ids = jnp.arange(2)
    weights = jnp.full((2,), 8.0)
    losses = []
    for r in range(6):
        st, m = seq_round(st, base, hdata, ids, weights,
                          jax.random.fold_in(jax.random.key(3), r))
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_fedllm_ulysses_round_converges():
    model = _tiny_lm()
    base = model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]
    t = TrainArgs(epochs=1, batch_size=4, learning_rate=0.5)
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=4)
    data = _lm_task(n_clients=2)
    mesh = make_mesh({"silos": 2, "seq": 4})
    seq_round = make_fedllm_seq_round(model, base, t, mesh, attn="ulysses")
    st = ServerState(jax.tree.map(jnp.array, adapters), None, jnp.int32(0), None)
    hdata = shard_fedllm_data(data, mesh)
    st, m = seq_round(st, base, hdata, jnp.arange(2), jnp.full((2,), 8.0),
                      jax.random.key(4))
    assert np.isfinite(float(m["train_loss"]))
