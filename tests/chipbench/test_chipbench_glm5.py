"""`glm5_744b_ep16` and its cell `glm5_decode_docqa`: what the configuration
file states against the catalog and against itself, its model builder and its
plain reference against each other at a small size on the CPU (logits on
seeded weights with contexts over `index_topk`; the shares of the expert
layer adding up to the uncut layer), its schedule, and its work functions
against counts made by hand. test_chipbench_glm5_rehearse.py drives the cell."""
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench import inputs, manifest
from chipbench.reference import glm5_744b_ep16 as reference

CELL_NAME = "glm5_decode_docqa"
MF = manifest.load_manifest()
CELL = manifest.Cell(MF, CELL_NAME)
CONFIG = CELL.config
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
WIDTHS = {"hidden_size": 6144, "intermediate_size": 12288,
          "moe_intermediate_size": 2048, "num_attention_heads": 64,
          "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
          "qk_rope_head_dim": 64, "qk_head_dim": 256, "v_head_dim": 256,
          "head_dim": 64, "index_n_heads": 32, "index_head_dim": 128,
          "index_topk": 2048, "num_experts_per_tok": 8, "n_shared_experts": 1,
          "router_num_experts": 256, "routed_scaling_factor": 2.5,
          "rms_norm_eps": 1e-05}


def small():
    _, _, model = CELL.sizes(rehearse=True)
    return model


# --------------------------------------------------------- the configuration
def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    m = CONFIG["model"]
    for key, value in WIDTHS.items():
        assert m[key] == value, key
    entry = {c["name"]: c for c in MF["configs"]}["glm5_744b_ep16"]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    assert (m["num_hidden_layers"], m["first_k_dense_replace"],
            m["n_routed_experts"], m["vocab_size"]) == (5, 1, 16, 19360)
    # the floors: the leading dense layers once and four followers, at
    # least 8 experts, at least an eighth of the vocabulary
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["n_routed_experts"] * m["expert_share"][1] == \
        m["router_num_experts"]
    assert m["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert {"norm_placement", "rope", "indexer_key_norm", "indexer_weights",
            "indexer_rope", "selection", "selection_bias"} <= set(
                CONFIG["assumed"])
    assert len(CONFIG["departures"]) == 3
    assert "multi_token_prediction" in CONFIG["left_out"]
    assert "16 chips share each layer" in CONFIG["deployment"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog beside the guide")
def test_the_file_holds_the_catalogs_config_key_for_key():
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "GLM-5")
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
    extra = {"router_num_experts", "expert_share", "compute_dtype"}
    model = CONFIG["model"]
    assert set(model) - extra <= set(CONFIG)
    assert extra <= set(model) and not extra & set(CONFIG)
    for key in set(model) - extra:
        assert CONFIG[key] == model[key], key
    assert len(set(model) - extra) == 39


def test_the_builder_gives_a_serving_spec_the_program_takes():
    from fedml_tpu.llm import decode
    from chipbench.models.glm_moe_dsa import held_experts

    lm, spec = manifest.find("models", "glm_moe_dsa")(small())
    assert decode.unserved(lm) == []
    assert spec["model_kind"] == "lm"
    assert lm.kinds == (("latent", "dense"),) + (("latent", "moe"),) * 2
    assert json.loads(json.dumps(spec["lm"])) == spec["lm"]   # plain data
    assert held_experts(small()) == (0, 4)
    with pytest.raises(ValueError, match="is not the router's"):
        held_experts({**small(), "expert_share": [0, 4]})
    with pytest.raises(ValueError, match="interleaved pairs"):
        manifest.find("models", "glm_moe_dsa")(
            {**small(), "rope_interleave": False})
    big, _ = manifest.find("models", "glm_moe_dsa")(CONFIG["model"])
    assert (big.latent.width, big.latent.scale) == (640, 1 / 16)


# ------------------------------------------------- program against reference
@pytest.fixture(scope="module")
def seeded():
    model = small()
    lm, _ = manifest.find("models", "glm_moe_dsa")(model)
    base = inputs.init_tree(inputs.param_shapes(lm), 11, 1.0, "float32")
    tokens, _ = inputs.token_rows(11, 1, 1, 40, model["vocab_size"])
    return model, lm, base, tokens[0, 0]


def test_program_logits_match_the_reference_on_seeded_weights(seeded):
    """40 positions against index_topk 8: all but the first eight queries
    select. The program's whole-sequence forward, and its decode programs
    through the latent pool (the stacked layout, a chunk of 32 then a token
    a step), both against the reference's full forward."""
    from fedml_tpu.llm import decode

    model, lm, base, tokens = seeded
    want = reference.forward(base, tokens, model)
    scale = float(jnp.max(jnp.abs(want)))
    got = lm.apply({"params": base}, tokens[None])[0]
    assert got.shape == want.shape == (40, model["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * scale
    stacked = decode.stack_blocks(base, model["num_hidden_layers"])
    np.testing.assert_allclose(
        reference.forward(stacked, tokens, model, rows=(30, 40)), want[30:],
        atol=1e-5 * scale)
    chunk, step, _v, _b = decode.make_paged_latent_decode(lm, 4)
    lat = lm.latent
    cache = {"kv": jnp.zeros((3, 17, 4, lat.width)),
             "ik": jnp.zeros((3, 17, 4, lat.index_dim))}
    row = jnp.arange(1, 17, dtype=jnp.int32)
    cache, logits = jax.jit(chunk)(stacked, None, cache, row,
                                   tokens[None, :32], 0, 32)
    assert float(jnp.max(jnp.abs(logits[0] - want[31]))) < 2e-4 * scale
    jstep = jax.jit(step)
    for t in range(32, 40):
        cache, logits = jstep(stacked, None, cache, row[None],
                              jnp.array([t]), tokens[t][None],
                              jnp.array([True]))
        assert float(jnp.max(jnp.abs(logits[0] - want[t]))) < 2e-4 * scale, t


def test_the_planted_faults_change_what_the_reference_selects(seeded):
    model, _lm, base, tokens = seeded
    kept = {}

    def run(**fault):
        sel = []
        logits = reference.forward(
            base, tokens, {**model, **fault}, rows=(32, 40),
            observe=lambda i, h, s: sel.append(np.asarray(s)))
        return np.asarray(logits), sel

    want, sound = run()
    assert all(s.shape == (8, 40) and (s.sum(-1) == 8).all() for s in sound)
    for fault in ({"fault": "selection_ignored"},
                  {"fault": "stale_index", "stale_from": 28}):
        off, theirs = run(**fault)
        assert any((a != b).any() for a, b in zip(theirs, sound)), fault
        assert np.abs(off - want).max() > 1e-4, fault
    # fp8 moves the logits by far more than float32 round-off
    low = np.asarray(reference.forward(base, tokens, model, "fp8",
                                       rows=(32, 40)))
    assert np.abs(low - want).max() > 1e-2


def test_the_shares_add_up_to_the_uncut_layer(seeded):
    """Guide section 4: the routed parts that all the shares give, with the
    shared expert counted once, are the uncut layer of the uncut reference;
    and the program's layer, told which share it holds, gives that share's
    part."""
    from fedml_tpu.llm.moe import ExpertLayer, MoE

    model = small()
    n_all, n_held = model["router_num_experts"], model["n_routed_experts"]
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
    uncut = {**model, "n_routed_experts": n_all, "expert_share": [0, 1]}
    whole, shared = reference.expert_layer(h, moe, uncut)
    parts = 0.0
    for s in range(shares):
        mine = {k: ({"kernel": v["kernel"][s * n_held:(s + 1) * n_held]}
                    if k.startswith("experts_") else v)
                for k, v in moe.items()}
        routed, shared_s = reference.expert_layer(
            h, mine, {**model, "expert_share": [s, shares]})
        np.testing.assert_allclose(shared_s, shared, atol=1e-6)
        parts = parts + routed
        spec = MoE(n_experts=n_all, top_k=model["num_experts_per_tok"],
                   d_expert=f, held=(s * n_held, n_held),
                   scale=model["routed_scaling_factor"])
        program = ExpertLayer(spec).apply({"params": mine}, h[None])[0]
        np.testing.assert_allclose(program, routed + shared, atol=2e-5)
    assert float(jnp.max(jnp.abs(whole))) > 0.1
    np.testing.assert_allclose(parts, whole, atol=2e-5)


# ------------------------------------------------------------ the cell
def test_the_routers_come_from_the_mix_and_the_rest_from_the_seed(seeded):
    model, lm, _base, _tokens = seeded
    assert CELL.traffic["routing_seed"] == 21

    def weights(seed):
        driver = manifest.find("drivers", "serve_docs")(CELL, seed, True)
        driver.shapes = inputs.param_shapes(lm)
        return driver.weights()

    a, b = weights(1), weights(2)
    for leaf in ("router", "e_score_correction_bias"):
        np.testing.assert_array_equal(
            jax.tree.leaves(a["block_1"]["moe"][leaf])[0],
            jax.tree.leaves(b["block_1"]["moe"][leaf])[0])
    assert not np.array_equal(a["block_1"]["wo"]["kernel"],
                              b["block_1"]["wo"]["kernel"])


def test_the_schedule_asks_every_document_four_times_by_the_clock():
    from chipbench.drivers.serve_docs import build_schedule

    mix = {k: v for k, v in CELL.traffic.items() if k != "rehearse"}
    a = build_schedule(mix, 40.0, 1, 19360)
    b = build_schedule(mix, 40.0, 2, 19360)
    assert [(r[0], len(r[1].tokens), r[1].max_new, r[2], r[3]) for r in a] \
        == [(r[0], len(r[1].tokens), r[1].max_new, r[2], r[3]) for r in b]
    assert a[0][1].tokens != b[0][1].tokens          # --seed: the ids alone
    assert a[0][0] >= -20.0 and all(x[0] <= y[0] for x, y in zip(a, a[1:]))
    assert max(r[0] for r in a) < 40.0
    docs = {}
    for due, plan, ask, question in a:
        doc = plan.tokens[:len(plan.tokens) - question]
        docs.setdefault(doc, []).append((ask, due))
        assert 8192 <= len(doc) <= 28672 and len(doc) % 16 == 0
        assert 32 <= question <= 256 and question % 16 == 0
        assert 16 <= plan.max_new <= 256
        assert min(plan.tokens) >= 1 and max(plan.tokens) < 19360
    for asks in docs.values():
        assert [k for k, _ in asks] == list(range(len(asks)))    # in order
        for (_, t0), (_, t1) in zip(asks, asks[1:]):
            assert 2.0 <= t1 - t0 <= 10.0
    assert any(len(v) == 4 for v in docs.values())


# ------------------------------------------------------ work, by hand
def test_the_work_functions_count_what_the_traffic_selects():
    from chipbench.work.glm5_decode_flops import key_flops, token_params

    m = CONFIG["model"]
    # ISSUE 34's reckoning: 165.0 M of attention and 9.4 M of indexer a
    # layer, 226.5 M of dense SwiGLU, 37.75 M an expert, the router 1.57 M
    attention = 12.58e6 + 33.55e6 + 3.54e6 + 14.68e6 + 100.66e6
    want = (5 * (attention + 9.4e6) + 226.5e6
            + 4 * (37.75e6 + 1.57e6 + 37.75e6 * 8 * 16 / 256)
            + 6144 * 19360)
    assert abs(token_params(m) - want) < 2e-3 * want
    assert key_flops(m) == (2.0 * 64 * (1024 + 64), 2.0 * 32 * 128)
    cell = types.SimpleNamespace(
        config={"model": m}, traffic={"serve": {"prefill_chunk": 512}})
    log = {"processed_tokens": 10, "selected_key_sum": 1000,
           "scored_key_sum": 500, "decode_selected_key_sum": 400,
           "decode_scored_key_sum": 200}
    flops = manifest.find("work", "glm5_decode_flops")(cell, log)["flops"]
    assert flops == 2.0 * token_params(m) * 10 + 5 * (
        139264.0 * 1000 + 8192.0 * 500)
    att = manifest.find("work", "latent_attention_traffic")(cell, log)
    assert att == {"flops": 5 * 139264.0 * 1000,
                   "bytes": 5 * 1152.0 * (400 + 600 / 512)}
    idx = manifest.find("work", "index_scores_traffic")(cell, log)
    assert idx == {"flops": 5 * 8192.0 * 500,
                   "bytes": 5 * 256.0 * (200 + 300 / 512)}


def test_the_docs_log_counts_hits_and_selected_keys():
    driver = manifest.find("drivers", "serve_docs")(CELL, 1, False)
    row = lambda n, times: types.SimpleNamespace(
        plan=types.SimpleNamespace(tokens=(1,) * n), token_times=times)
    # an opening ask of 4,096 + 32 tokens and a follow-up of the same
    # length, both with their first token and one more in the span
    rows = [row(4128, [1.0, 1.5, 9.0]), row(4128, [2.0, 2.5])]
    log = driver.docs_log(rows, [(0, 32), (1, 32)], 0.5, 3.0)
    assert log["admitted"] == 2 and log["emitted_tokens"] == 2
    assert log["prefilled_tokens"] == 4128 + 32
    assert log["processed_tokens"] == 4128 + 32 + 2
    seen = np.arange(1, 4129)
    opening = np.minimum(seen, 2048).sum()
    follow = 32 * 2048
    assert log["selected_key_sum"] == opening + follow + 2 * 2048
    assert log["decode_selected_key_sum"] == 2 * 2048
    assert log["decode_scored_key_sum"] == 2 * 4129
    assert log["scored_key_sum"] == seen[seen > 2048].sum() + np.arange(
        4097, 4129).sum() + 2 * 4129
