"""`model_type: glm_moe_dsa`: GLM-5's decoder on `fedml_tpu.llm.TransformerLM`:
latent attention under a learned sparse selection in every layer
(`fedml_tpu/llm/latent.py`), `first_k_dense_replace` dense layers and then
expert layers, a sigmoid router over `router_num_experts` of which this chip
holds `n_routed_experts`, share `expert_share[0]` of `expert_share[1]`.
configs/glm5_744b_ep16.json says what the config leaves open and what is
left out. The layers stay unrolled: their parameters differ by kind.

`build` returns the module and the model part of `start_replica`'s spec: the
`lm` recipe carries the same fields the module was made from, as plain data."""
from __future__ import annotations


def held_experts(model: dict) -> tuple:
    """(first, count) of the experts held here."""
    share, of = model["expert_share"]
    if model["n_routed_experts"] * of != model["router_num_experts"]:
        raise ValueError(
            f"{model['n_routed_experts']} experts held x {of} shares is not "
            f"the router's {model['router_num_experts']}")
    return share * model["n_routed_experts"], model["n_routed_experts"]


def recipe(model: dict) -> dict:
    """The `lm` recipe `serving.scheduler.start_replica` builds the model
    from (TransformerLM's fields, `latent` and `moe` as their dataclasses'
    fields)."""
    if (model["scoring_func"], model["topk_method"], model["n_group"],
            model["topk_group"]) != ("sigmoid", "noaux_tc", 1, 1):
        raise ValueError("the expert layer routes by sigmoid scores plus a "
                         "selection bias with no group limit (noaux_tc, "
                         "n_group = topk_group = 1)")
    if not (model["rope_interleave"] and model["indexer_rope_interleave"]):
        raise ValueError("llm/latent.py rotates interleaved pairs, in "
                         "attention and in the indexer")
    n, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    return {
        "vocab_size": model["vocab_size"], "d_model": model["hidden_size"],
        "n_layers": n, "n_heads": model["num_attention_heads"],
        "d_ff": model["intermediate_size"],
        "norm_eps": model["rms_norm_eps"],
        "rope_base": float(model["rope_parameters"]["rope_theta"]),
        "latent": {
            "q_rank": model["q_lora_rank"], "kv_rank": model["kv_lora_rank"],
            "nope": model["qk_nope_head_dim"],
            "rope": model["qk_rope_head_dim"], "v_dim": model["v_head_dim"],
            "index_heads": model["index_n_heads"],
            "index_dim": model["index_head_dim"],
            "index_topk": model["index_topk"]},
        "moe": {
            "n_experts": model["router_num_experts"],
            "top_k": model["num_experts_per_tok"],
            "d_expert": model["moe_intermediate_size"],
            "held": list(held_experts(model)),
            "n_shared": model["n_shared_experts"],
            "scale": model["routed_scaling_factor"],
            "norm_topk": model["norm_topk_prob"]},
        "layer_kinds": [["latent", "dense" if i < dense else "moe"]
                        for i in range(n)]}


def build(model: dict, **options):
    from fedml_tpu.llm.latent import Latent
    from fedml_tpu.llm.moe import MoE
    from fedml_tpu.llm.transformer import TransformerLM

    lm = recipe(model)
    moe = dict(lm["moe"], held=tuple(lm["moe"]["held"]))
    module = TransformerLM(
        **{k: v for k, v in lm.items()
           if k not in ("latent", "moe", "layer_kinds")},
        latent=Latent(**lm["latent"]), moe=MoE(**moe),
        layer_kinds=tuple(tuple(k) for k in lm["layer_kinds"]), **options)
    return module, {"model_kind": "lm", "lm": lm}
