"""`kexaone_236b_ep8`: what its configuration file states, its model builder
and its plain reference against each other at a small size on the CPU (logits
on seeded weights through a dense, a window and a full layer; the shares of
the expert layer adding up to the uncut layer), and its work functions and
its reducer against counts made by hand."""
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import kexaone_236b_ep8 as reference
from chipbench.reference.common import HI

MF = manifest.load_manifest()
CELL = manifest.Cell(MF, "kexaone_fedlora_s4x8k")
CONFIG = CELL.config
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
WIDTHS = {"hidden_size": 6144, "intermediate_size": 18432,
          "moe_intermediate_size": 2048, "head_dim": 128,
          "num_attention_heads": 64, "num_key_value_heads": 8,
          "num_experts_per_tok": 8, "num_shared_experts": 1,
          "sliding_window": 128, "router_num_experts": 128,
          "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05}


def small():
    """The configuration at its rehearsal sizes, float32."""
    _, _, model = CELL.sizes(rehearse=True)
    return model


def mm(a, b):
    return jnp.matmul(a, jnp.asarray(b, jnp.float32), precision=HI)


# --------------------------------------------------------- the configuration
def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    m = CONFIG["model"]
    for key, value in WIDTHS.items():
        assert m[key] == value, key
    entry = {c["name"]: c for c in MF["configs"]}["kexaone_236b_ep8"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 153600}
    assert (m["num_hidden_layers"], m["num_experts"], m["vocab_size"]) == (
        5, 16, 19200)
    # the floors: a whole period and four followers of the dense layer, at
    # least 8 experts, at least an eighth of the vocabulary
    assert m["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert m["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert m["num_experts"] * m["expert_share"][1] == m["router_num_experts"]
    assert m["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert {"norm_placement", "qk_norm", "rope", "selection_bias"} <= set(
        CONFIG["assumed"])
    assert "multi_token_prediction" in CONFIG["left_out"]
    assert "8 chips share each layer" in CONFIG["deployment"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog beside the guide")
def test_the_file_holds_the_catalogs_config_key_for_key():
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if '"K-EXAONE-236B-A23B"' in l)
    assert CONFIG["source"] == row["source_url"]
    # at the TOP of the file, where the benchmark's check reads them: a key
    # BENCHMARK.json lists under `reduced` as run, every other as published
    for key, value in row["config"].items():
        if key in CONFIG["published"]:
            assert CONFIG["published"][key] == value, key
            assert key in CONFIG and CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


def test_the_model_group_repeats_the_top_of_the_file_value_for_value():
    """The check reads the published keys at the top of the file, the
    harness hands the `model` group to everything that runs: they may not
    part."""
    extra = {"router_num_experts", "expert_share", "compute_dtype"}
    model = CONFIG["model"]
    assert set(model) - extra <= set(CONFIG)
    assert extra <= set(model) and not extra & set(CONFIG)
    for key in set(model) - extra:
        assert CONFIG[key] == model[key], key
    assert len(set(model) - extra) == 31


def test_the_rehearsal_keeps_a_dense_a_window_and_a_full_layer():
    from chipbench.models.exaone_moe import held_experts, layer_kinds

    model = small()
    kinds = layer_kinds(model)
    assert ("window", "dense") in kinds and ("window", "moe") in kinds \
        and ("full", "moe") in kinds
    assert model["num_attention_heads"] == 2 * model["num_key_value_heads"]
    assert (model["router_num_experts"], model["num_experts"]) == (8, 4)
    assert held_experts(model) == (0, 4)
    assert held_experts({**model, "expert_share": [1, 2]}) == (4, 4)
    with pytest.raises(ValueError, match="is not the router's"):
        held_experts({**model, "expert_share": [0, 4]})


def test_the_builder_gives_no_serving_spec_and_the_program_refuses_it():
    from fedml_tpu.llm import decode

    lm, spec = manifest.find("models", "exaone_moe")(small())
    assert spec is None
    assert len(decode.unserved(lm)) == 4


def test_the_dense_builder_still_refuses_grouped_heads_by_name():
    build = manifest.find("models", "olmo")
    with pytest.raises(ValueError, match="2 KV heads of 16.*model builder "
                       "of its own"):
        build({"num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "hidden_size": 64, "vocab_size": 8,
               "num_hidden_layers": 1, "intermediate_size": 8})


# ------------------------------------------------- program against reference
@pytest.fixture(scope="module")
def seeded():
    from fedml_tpu.ops.flash_attention import flash_attn_fn

    model = small()
    lm, _ = manifest.find("models", "exaone_moe")(model,
                                                  attn_fn=flash_attn_fn)
    base = inputs.init_tree(inputs.param_shapes(lm), 11, 1.0, "float32")
    tokens, _ = inputs.token_rows(11, 1, 1, 32, model["vocab_size"])
    return model, lm, base, tokens[0, 0]


def test_program_logits_match_the_reference_on_seeded_weights(seeded):
    model, lm, base, tokens = seeded
    got = lm.apply({"params": base}, tokens[None])[0]
    want = reference.forward(base, tokens, model)
    assert got.shape == want.shape == (32, model["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(
        jnp.max(jnp.abs(want)))


def test_the_planted_faults_change_the_references_logits(seeded):
    model, _lm, base, tokens = seeded
    want = reference.forward(base, tokens, model)
    for fault in ("drop_expert", "unnormalised"):
        off = reference.forward(base, tokens, {**model, "fault": fault})
        assert float(jnp.max(jnp.abs(off - want))) > 1e-2, fault


def test_the_shares_add_up_to_the_uncut_layer(seeded):
    """Guide section 4: the routed parts that all the shares give, with the
    shared expert counted once, are the uncut layer of the uncut reference;
    and the program's layer, told which share it holds, gives that share's
    part."""
    from fedml_tpu.llm.moe import ExpertLayer, MoE

    model = small()
    n_all, n_held = model["router_num_experts"], model["num_experts"]
    shares = n_all // n_held
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    moe = dict(seeded[2]["block_1"]["moe"])
    key = jax.random.key(5)
    for i, (name, shape) in enumerate([("w_gate", (n_all, d, f)),
                                       ("w_up", (n_all, d, f)),
                                       ("w_down", (n_all, f, d))]):
        moe[f"experts_{name}"] = {"kernel": jax.random.normal(
            jax.random.fold_in(key, i), shape) / np.sqrt(shape[-2])}
    h = jax.random.normal(jax.random.fold_in(key, 9), (48, d))
    uncut = {**model, "num_experts": n_all, "expert_share": [0, 1]}
    whole, shared = reference.expert_layer(h, moe, uncut, mm)

    parts = 0.0
    for s in range(shares):
        mine = {k: ({"kernel": v["kernel"][s * n_held:(s + 1) * n_held]}
                    if k.startswith("experts_") else v)
                for k, v in moe.items()}
        routed, shared_s = reference.expert_layer(
            h, mine, {**model, "expert_share": [s, shares]}, mm)
        np.testing.assert_allclose(shared_s, shared, atol=1e-6)
        parts = parts + routed
        spec = MoE(n_experts=n_all, top_k=model["num_experts_per_tok"],
                   d_expert=f, held=(s * n_held, n_held),
                   scale=model["routed_scaling_factor"])
        program = ExpertLayer(spec).apply({"params": mine}, h[None])[0]
        np.testing.assert_allclose(program, routed + shared, atol=2e-5)
    assert float(jnp.max(jnp.abs(whole))) > 0.1
    np.testing.assert_allclose(parts + shared, whole + shared, atol=2e-5)


# ------------------------------------------------------ work, by hand
TINY = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 4, "num_hidden_layers": 2, "sliding_window": 2,
        "layer_types": ["sliding_attention", "full_attention", "x"],
        "mlp_layer_types": ["dense", "sparse", "x"], "intermediate_size": 16,
        "moe_intermediate_size": 4, "router_num_experts": 8, "num_experts": 2,
        "num_shared_experts": 1, "vocab_size": 32}
TRAFFIC = {"seq_len": 4, "lora_rank": 2, "lora_targets": ["wq", "wo"],
           "batch_size": 1, "remat": True}
LOG = {"tokens": 8, "moe_pairs": 5}


def tiny_cell():
    return types.SimpleNamespace(config={"model": TINY}, traffic=TRAFFIC)


def test_the_blocks_training_flops_by_hand():
    projections = 8 * 16 + 8 * 8 + 8 * 8 + 16 * 8                  # 384
    per_token = (8 * 32 + projections + 3 * 8 * 16                  # dense
                 + projections + 8 * 8 + 3 * 8 * 4)                 # sparse
    adapters = 2 * (3 * 2 * 2 * (8 + 16) + 3 * 2 * 2 * (16 + 8))
    band, half = 4 * 2 - 1, 4 * 5 // 2          # pairs scored a sequence
    scores = 2 * 2 * 16 * band + 2 * 2 * 16 * half
    per_seq = 4 * per_token * 4 + adapters * 4 + 3 * scores
    got = manifest.find("work", "kexaone_train_flops")(tiny_cell(), LOG)
    assert got == {"flops": per_seq * 2 + 2 * 6 * 8 * 4 * 5, "bytes": 0.0}


def test_the_expert_products_work_follows_the_pairs_routed_here():
    got = manifest.find("work", "expert_matmul_work")(tiny_cell(), LOG)
    # 3 passes (remat); 2 sequences x 1 sparse layer read 2 experts' weights
    assert got == {"flops": 3 * 6 * 8 * 4 * 5,
                   "bytes": 3 * (2 * 3 * 2 * 8 * 4 * 2
                                 + 5 * (2 * 8 + 3 * 4) * 2)}
    more = manifest.find("work", "expert_matmul_work")(
        tiny_cell(), {**LOG, "moe_pairs": 10})
    assert more["flops"] == 2 * got["flops"]


@pytest.mark.parametrize("name,flops,bytes_", [
    ("flash_gqa_fwd_call", 2 * 2 * 16 * 10, (2 * 64 + 2 * 32) * 2),
    ("flash_gqa_bwd_call", 5 * 2 * 16 * 10, (4 * 64 + 4 * 32) * 2),
    ("flash_window_fwd_call", 2 * 2 * 16 * 7, (2 * 64 + 2 * 32) * 2),
    ("flash_window_bwd_call", 5 * 2 * 16 * 7, (4 * 64 + 4 * 32) * 2)])
def test_a_flash_call_by_hand(name, flops, bytes_):
    # 4 tokens, 4 heads of 4 over 2 KV heads: q-sized 64, k-sized 32
    # elements; the causal half holds 10 pairs, the band of 2 holds 7
    assert manifest.find("work", name)(tiny_cell(), {}) == {
        "flops": flops, "bytes": bytes_}


def test_scope_roofline_reads_every_operation_under_the_scope():
    path = "jit(round_body)/fed.collect/vmap(fed.local_sgd)/{}/lm.mlp/{}/dot"
    trace = {"host": [["chipbench.window", 0, 10000]], "chips": [{
        "programs": [["jit_round_body(3)", 0, 10000]],
        "ops": [["fusion.1", 1000, 1000], ["gmm.2", 3000, 3000],
                ["fusion.3", 7000, 1000], ["while.4", 0, 9000]],
        "scopes": {"jit_round_body": {
            "fusion.1": path.format("jvp(TransformerLM)", "moe.experts"),
            "gmm.2": path.format("transpose(jvp(TransformerLM))",
                                 "moe.experts"),
            "fusion.3": path.format("jvp(TransformerLM)", "moe.route")}}}]}
    spec = CELL.metric_file("expert_matmul_roofline")
    ctx = {"cell": tiny_cell(), "log": LOG,
           "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}}
    reducer = manifest.find("reducers", spec["reducer"])
    # bytes bind: 3,144 B at 1 GB/s = 3.144 us, over 1 + 3 us under the scope
    assert reducer(spec, trace, ctx) == pytest.approx(100 * 3.144 / 4.0)
    nothing = {**trace, "chips": [{**trace["chips"][0], "scopes": {}}]}
    assert reducer(spec, nothing, ctx) is None


def test_the_new_cells_metrics_each_have_a_file_and_a_reader():
    names = {m["name"] for m in manifest.metrics_for(MF, CELL.name, True)}
    assert names == {
        "round_device_ms.fedlora", "recompute_share.fedlora",
        "mfu.fedlora_moe", "moe_share.fedlora", "moe_route_share.fedlora",
        "expert_matmul_roofline", "flash_window_fwd_roofline",
        "flash_window_bwd_roofline", "flash_gqa_fwd_roofline",
        "flash_gqa_bwd_roofline"}
    for name in names:
        spec = CELL.metric_file(name)
        assert callable(manifest.find("reducers", spec["reducer"]))
    # the dense block's counts are not pointed at this cell
    assert not {"mfu.fedlora", "flash_fwd_roofline",
                "flash_bwd_roofline"} & names
    # a full call's pattern does not match a windowed kernel's events
    from chipbench import reduce
    events = [["flash_fwd.3", 0, 1], ["flash_fwd_window.4", 0, 1],
              ["flash_bwd_dq_window.5", 0, 1], ["flash_bwd_dkv.6", 0, 1]]
    pick = lambda m: [e[0] for e in reduce.matching(
        events, CELL.metric_file(m)["kernels"])]
    assert pick("flash_gqa_fwd_roofline") == ["flash_fwd.3"]
    assert pick("flash_window_fwd_roofline") == ["flash_fwd_window.4"]
    assert pick("flash_gqa_bwd_roofline") == ["flash_bwd_dkv.6"]
    assert pick("flash_window_bwd_roofline") == ["flash_bwd_dq_window.5"]
