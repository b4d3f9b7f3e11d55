"""One partitioning plane (parallel/partition.py, ISSUE 6).

The contracts:
- golden resolved-spec table for the flagship TransformerLM: every param
  matched (unmatched is a hard error), the KV-cache spec included;
- ambiguity is a HARD error (two rules, different specs), never
  first-match-silently-wins;
- train and serve resolve the SAME table: round-program/trainer specs ==
  DecodeEngine specs for identical trees;
- the mp=1 engine stays token-identical to the unmeshed engine AND the
  per-request path (pinned);
- on a 2-device CPU mesh (conftest forces 8 virtual devices;
  XLA_FLAGS=--xla_force_host_platform_device_count), sharded train-step
  and engine outputs match the unsharded ones;
- llm/tp.py's tp_param_specs is a deprecation shim over the registry;
- make_mesh names the offending axis on bad shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.quant import quantize_tree_int8
from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.parallel import partition
from fedml_tpu.parallel.mesh import make_mesh
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import GreedyLMPredictor

V, D, L, H, FF = 96, 64, 2, 4, 128
MAXLEN = 32


def _flagship(scan=True, seed=0):
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=scan)
    params = model.init(jax.random.key(seed),
                        jnp.zeros((1, 10), jnp.int32))["params"]
    return model, params


def _prompts(ns, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, V, n).tolist() for n in ns]


# --------------------------------------------------------- golden table
def test_flagship_golden_resolved_table():
    """The flagship TransformerLM (scan layout, int8 base — the 7B serving
    shape) resolves under the DEFAULT error policy (=> every param
    matched) to the pinned Megatron table; the KV-cache spec is part of
    the same plane."""
    _model, params = _flagship(scan=True)
    specs = partition.resolve("transformer_lm", quantize_tree_int8(params))
    golden = {
        "blocks/RMSNorm_0/scale": P(),
        "blocks/RMSNorm_1/scale": P(),
        "blocks/wq/kernel/q": P(None, None, "mp"),
        "blocks/wq/kernel/s": P(None, None, "mp"),
        "blocks/wk/kernel/q": P(None, None, "mp"),
        "blocks/wk/kernel/s": P(None, None, "mp"),
        "blocks/wv/kernel/q": P(None, None, "mp"),
        "blocks/wv/kernel/s": P(None, None, "mp"),
        "blocks/w_gate/kernel/q": P(None, None, "mp"),
        "blocks/w_gate/kernel/s": P(None, None, "mp"),
        "blocks/w_up/kernel/q": P(None, None, "mp"),
        "blocks/w_up/kernel/s": P(None, None, "mp"),
        "blocks/wo/kernel/q": P(None, "mp", None),
        "blocks/wo/kernel/s": P(),
        "blocks/w_down/kernel/q": P(None, "mp", None),
        "blocks/w_down/kernel/s": P(),
        "embed/embedding/q": P(None, "mp"),
        "embed/embedding/s": P(),
        "final_norm/scale": P(),
        "lm_head/kernel/q": P(None, "mp"),
        "lm_head/kernel/s": P(),
    }
    flat = {partition.path_name(path): spec for path, spec in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert flat == golden
    # the serve-side KV pool shards the heads axis of [L, P, page, H, Dh]
    assert partition.paged_kv_cache_spec("mp") == P(
        None, None, None, "mp", None)
    # unrolled float layout also fully covered (no UnmatchedParamError)
    _m2, p2 = _flagship(scan=False)
    partition.resolve("transformer_lm", p2)
    # LoRA adapters resolve REPLICATED through their own table
    ads = lora_init(jax.random.key(1), p2, rank=4)
    assert all(s == P() for s in
               jax.tree.leaves(partition.resolve("lora", ads)))


def test_unmatched_param_policy():
    params = {"mystery/kernel": jnp.zeros((4, 4))}
    with pytest.raises(partition.UnmatchedParamError, match="mystery"):
        partition.resolve("transformer_lm", params)
    # replicated is an explicit opt-in, never the silent default
    specs = partition.resolve("transformer_lm", params,
                              on_unmatched=partition.REPLICATED)
    assert specs["mystery/kernel"] == P()
    # scalars/size-1 leaves never consult the table (nothing to shard)
    assert partition.match_partition_rules(
        (), {"step": jnp.zeros(())})["step"] == P()


def test_ambiguous_rules_hard_error():
    params = {"wq/kernel": jnp.zeros((8, 8))}
    rules = ((r"wq", P(None, "mp")), (r"kernel$", P("mp", None)))
    with pytest.raises(partition.AmbiguousRuleError, match="wq/kernel"):
        partition.match_partition_rules(rules, params)
    # two rules AGREEING on the spec is not ambiguity
    ok = ((r"wq", P(None, "mp")), (r"kernel$", P(None, "mp")))
    assert partition.match_partition_rules(ok, params)["wq/kernel"] == \
        P(None, "mp")
    # same pattern twice with different specs dies at table load, before
    # any param is consulted
    with pytest.raises(partition.AmbiguousRuleError, match="twice"):
        partition.match_partition_rules(
            ((r"x", P()), (r"x", P("mp"))), params)
    # a spec with more axes than the leaf has dims names the rule
    with pytest.raises(partition.PartitionRuleError, match="rank"):
        partition.match_partition_rules(
            ((r"kernel", P(None, None, None, "mp")),), params)
    # a broken regex fails at load with the pattern named
    with pytest.raises(partition.PartitionRuleError, match="valid regex"):
        partition.match_partition_rules(((r"(", P()),), params)


def test_explain_prints_resolved_table():
    _model, params = _flagship(scan=True)
    out = partition.explain(partition.transformer_lm_rules("mp"), params)
    assert "blocks/wq/kernel" in out
    assert "PartitionSpec(None, None, 'mp')" in out
    # every line carries the rule that produced the spec
    assert all("[" in line for line in out.splitlines())


# ------------------------------------------------- one table, two planes
def test_train_and_serve_spec_tables_identical():
    """The round-program/trainer entry point and the DecodeEngine resolve
    to the SAME spec table for the flagship model — the anti-drift
    contract for train/serve checkpoints."""
    from fedml_tpu.llm.decode import lay_out_blocks
    from fedml_tpu.parallel.round import resolve_param_specs

    model, params = _flagship(scan=True)
    # the engine holds wq|wk|wv as one kernel, a column group a device
    # (llm/decode.py lay_out_blocks): the same table resolves that tree
    train_specs = resolve_param_specs(lay_out_blocks(params, 2),
                                      "transformer_lm", axis="mp")
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       mesh=make_mesh({"mp": 2})).start()
    try:
        assert jax.tree.map(lambda a, b: tuple(a) == tuple(b),
                            train_specs, eng.param_specs) == \
            jax.tree.map(lambda _: True, train_specs)
        assert tuple(eng.param_specs["blocks"]["wqkv"]["kernel"]) == (
            None, None, "mp", None)
        # and the engine's weights/cache are genuinely laid out that way
        wq = eng.params["blocks"]["wqkv"]["kernel"]
        assert len(wq.sharding.device_set) == 2
        assert "mp" in str(eng._carry["cache"]["k"].sharding.spec)
    finally:
        eng.stop()


def test_tp_shim_delegates_to_registry():
    from fedml_tpu.llm import tp

    _model, params = _flagship(scan=False)
    old = tp.tp_param_specs(params)            # legacy axis name "tp"
    new = partition.resolve("transformer_lm", params, axis="tp")
    assert jax.tree_util.tree_flatten(
        jax.tree.map(lambda a, b: tuple(a) == tuple(b), old, new))[0] == \
        [True] * len(jax.tree.leaves(old))
    # legacy behavior preserved: params the table misses replicate
    assert tp.tp_param_specs({"odd/leaf": jnp.zeros((3, 3))})["odd/leaf"] \
        == P()
    assert "DEPRECATED" in tp.tp_param_specs.__doc__


def test_shard_fed_data_resolves_through_registry():
    from fedml_tpu.parallel.round import shard_fed_data

    mesh = make_mesh({"clients": 4})
    data = {"x": np.zeros((8, 4, 3), np.float32),
            "y": np.zeros((8, 4), np.int32),
            "mask": np.ones((8, 4), np.float32)}
    out = shard_fed_data(data, mesh)
    assert str(out["x"].sharding.spec) == "PartitionSpec('clients',)"
    # an unexpected data key is a loud registry error, not a silently
    # replicated transfer
    with pytest.raises(partition.UnmatchedParamError, match="weights"):
        shard_fed_data({**data, "weights": np.ones((8,))}, mesh)


# ---------------------------------------------------- mesh equivalence
def test_mesh_train_step_matches_unsharded():
    """2-device mp mesh: registry-sharded train step == unsharded step
    (the sharded-train acceptance leg of the 2x1 equivalence test)."""
    from fedml_tpu.llm.tp import make_tp_train_step
    from fedml_tpu.parallel.round import shard_server_params

    model, params = _flagship(scan=False, seed=1)
    rs = np.random.RandomState(0)
    seqs = rs.randint(0, V, (8, 17))
    x = jnp.asarray(seqs[:, :-1], jnp.int32)
    y = jnp.asarray(seqs[:, 1:], jnp.int32)

    step_ref = make_tp_train_step(model, make_mesh({"dp": 1, "tp": 1}),
                                  lr=0.1, dp_axis=None)
    p_ref, loss_ref = step_ref(params, x, y)

    mesh = make_mesh({"dp": 1, "mp": 2})
    sharded = shard_server_params(params, mesh, "transformer_lm")
    wq = sharded["block_0"]["wq"]["kernel"]
    assert len(wq.sharding.device_set) == 2

    import optax

    @jax.jit
    def step(p, tokens, targets):
        def loss_fn(q):
            logits = model.apply({"params": q}, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, targets).mean()
        loss, grads = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), loss

    p_mp, loss_mp = step(sharded, x, y)
    np.testing.assert_allclose(float(loss_mp), float(loss_ref),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(p_mp), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_engine_mp1_and_mp2_token_identical_to_unmeshed():
    """The engine acceptance pin: greedy output on an mp=1 mesh AND an
    mp=2 mesh is token-identical to the unmeshed engine and the
    per-request path — 5 requests retiring at different steps through 2
    slots, so admission/retirement cross the sharded admit/step programs
    mid-flight."""
    model, params = _flagship(scan=True)
    prompts = _prompts((6, 10, 8, 5, 7))
    budgets = [4, 7, 5, 6, 3]
    per_req = GreedyLMPredictor(model, params, max_len=MAXLEN,
                                kv_cache=True)
    want = [per_req.predict({"tokens": p, "max_new_tokens": b})
            ["generated_tokens"] for p, b in zip(prompts, budgets)]

    def run(mesh):
        eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                           mesh=mesh).start()
        try:
            ts = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
            return [t.result(timeout=120) for t in ts]
        finally:
            eng.stop()

    assert run(None) == want                        # current engine pin
    assert run(make_mesh({"mp": 1})) == want        # mp=1 pin
    assert run(make_mesh({"mp": 2})) == want        # tensor-parallel pin


def test_engine_mesh_validation():
    model, params = _flagship(scan=True)
    with pytest.raises(ValueError, match="no 'mp' axis"):
        DecodeEngine(model, params, n_slots=1, max_len=MAXLEN,
                     mesh=make_mesh({"dp": 2}))
    with pytest.raises(ValueError, match="divisible"):
        DecodeEngine(model, params, n_slots=1, max_len=MAXLEN,
                     mesh=make_mesh({"mp": 3}))
    with pytest.raises(partition.PartitionRuleError, match="no 'mp' axis"):
        partition.shard_params(params, make_mesh({"dp": 2}),
                               "transformer_lm")


def test_predictor_engine_mp_knob():
    """serve-knob plumbing: engine_mp=2 brings the engine up
    tensor-parallel via lm_predictor_from_serve_knobs (the one mapping the
    config route and start_replica share), token-identical output."""
    from fedml_tpu.config import Config
    from fedml_tpu.serving.predictor import lm_predictor_from_serve_knobs

    model, params = _flagship(scan=True)
    prompt = _prompts((7,))[0]
    cfg = Config.from_dict({"serve": {"decode_slots": 2,
                                      "engine_max_len": MAXLEN,
                                      "engine_mp": 2}})
    pred = lm_predictor_from_serve_knobs(cfg.serve_args.extra, model,
                                         params)
    try:
        assert pred.engine.mesh is not None
        assert pred.engine.mesh.shape["mp"] == 2
        want = GreedyLMPredictor(model, params, max_len=MAXLEN,
                                 kv_cache=True).predict(
            {"tokens": prompt, "max_new_tokens": 5})
        assert pred.predict({"tokens": prompt, "max_new_tokens": 5}) == want
    finally:
        pred.stop()
    with pytest.raises(ValueError, match="engine_mp"):
        Config.from_dict({"serve": {"engine_mp": 0}})
    # engine_mp without the engine would be silently ignored — refused
    with pytest.raises(ValueError, match="decode_slots"):
        Config.from_dict({"serve": {"engine_mp": 2}})


# ------------------------------------------------- centralized trainer
def test_centralized_trainer_mp_mesh_matches_unsharded():
    import fedml_tpu
    from fedml_tpu.centralized import CentralizedTrainer

    base = {
        "data_args": {"dataset": "synthetic",
                      "extra": {"synthetic_samples_per_client": 32}},
        "model_args": {"model": "mlp"},
        "train_args": {"client_num_in_total": 4, "client_num_per_round": 4,
                       "epochs": 1, "batch_size": 16,
                       "learning_rate": 0.3},
    }
    tr0 = CentralizedTrainer(fedml_tpu.init(config=base))
    h0 = tr0.run(epochs=2)
    cfg = fedml_tpu.init(config={
        **base, "device_args": {"mesh_shape": {"mp": 2}}})
    tr1 = CentralizedTrainer(cfg)
    # the registry resolved the auto-picked mlp_cnn table
    assert tr1.param_specs["Dense_0"]["kernel"] == P(None, "mp")
    h1 = tr1.run(epochs=2)
    for a, b in zip(h0, h1):
        np.testing.assert_allclose(a["train_loss"], b["train_loss"],
                                   rtol=1e-4, atol=1e-6)
    for x, y in zip(jax.tree.leaves(tr0.params),
                    jax.tree.leaves(tr1.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)
    # the epoch output layout is PINNED to the registry specs (the
    # compiler must not drift a leaf to its own choice of sharding)
    flat_s = {partition.path_name(p): s for p, s in
              jax.tree_util.tree_flatten_with_path(tr1.param_specs)[0]}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tr1.params)[0]:
        assert tuple(leaf.sharding.spec) == \
            tuple(flat_s[partition.path_name(path)])


def test_config_partition_knob_validation():
    from fedml_tpu.config import Config

    cfg = Config.from_dict({"device_args": {
        "partition_rules": "transformer_lm", "unmatched_params": "error"}})
    assert cfg.device_args.extra["partition_rules"] == "transformer_lm"
    with pytest.raises(ValueError, match="partition_rules"):
        Config.from_dict({"device_args": {"partition_rules": "transfomer"}})
    with pytest.raises(ValueError, match="unmatched_params"):
        Config.from_dict({"device_args": {"unmatched_params": "ignore"}})


# -------------------------------------------------------- mesh hygiene
def test_make_mesh_names_offending_axis():
    devs = jax.devices()
    with pytest.raises(ValueError, match="'tp'"):
        make_mesh({"dp": 2, "tp": 0}, devices=devs)
    with pytest.raises(ValueError, match="'mp'"):
        make_mesh({"dp": 2, "mp": "four"}, devices=devs)
    with pytest.raises(ValueError, match="both -1"):
        make_mesh({"a": -1, "b": -1}, devices=devs)
    # -1 that cannot divide the device count names the wildcard axis
    with pytest.raises(ValueError, match="'rest'"):
        make_mesh({"a": 3, "rest": -1}, devices=devs)
    with pytest.raises(ValueError, match="'tp'"):
        make_mesh({"dp": 2, "tp": 16}, devices=devs)
    # the valid shapes all still build
    assert make_mesh({"dp": 2, "mp": -1}, devices=devs).shape["mp"] == 4
    assert make_mesh({"mp": 2}, devices=devs).shape["mp"] == 2
