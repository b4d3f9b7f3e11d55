"""`model_type: exaone_moe`: K-EXAONE's decoder on `fedml_tpu.llm.TransformerLM`
(grouped KV heads of `head_dim`, window and full layers by `layer_types`,
a dense first layer and expert layers by `mlp_layer_types`, a sigmoid
router over `router_num_experts` of which this chip holds `num_experts`,
share `expert_share[0]` of `expert_share[1]`). configs/kexaone_236b_ep8.json
says what the config leaves open and what is left out. The layers stay
unrolled: their parameters differ by kind.

The program cannot serve this model (grouped heads in the paged kernel,
window layers in the page allocator, experts in the decode step: ROADMAP
"cannot run yet"), so the spec for `start_replica` is None."""
from __future__ import annotations

ATTENTION = {"sliding_attention": "window", "full_attention": "full"}
FEED_FORWARD = {"dense": "dense", "sparse": "moe"}


def layer_kinds(model: dict) -> tuple:
    """((attention, feed-forward), ...) of the first `num_hidden_layers`
    layers of the published pattern."""
    n = model["num_hidden_layers"]
    return tuple((ATTENTION[a], FEED_FORWARD[f]) for a, f in zip(
        model["layer_types"][:n], model["mlp_layer_types"][:n]))


def held_experts(model: dict) -> tuple:
    """(first, count) of the experts held here."""
    share, of = model["expert_share"]
    if model["num_experts"] * of != model["router_num_experts"]:
        raise ValueError(
            f"{model['num_experts']} experts held x {of} shares is not the "
            f"router's {model['router_num_experts']}")
    return share * model["num_experts"], model["num_experts"]


def build(model: dict, **options):
    from fedml_tpu.llm.moe import MoE
    from fedml_tpu.llm.transformer import TransformerLM

    if (model["scoring_func"], model["n_group"], model["topk_group"]) != (
            "sigmoid", 1, 1):
        raise ValueError("the expert layer routes by sigmoid scores with no "
                         "group limit (n_group = topk_group = 1)")
    moe = MoE(n_experts=model["router_num_experts"],
              top_k=model["num_experts_per_tok"],
              d_expert=model["moe_intermediate_size"],
              held=held_experts(model), n_shared=model["num_shared_experts"],
              scale=model["routed_scaling_factor"],
              norm_topk=model["norm_topk_prob"])
    lm = TransformerLM(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        d_ff=model["intermediate_size"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        norm_eps=model["rms_norm_eps"],
        rope_base=float(model["rope_parameters"]["rope_theta"]),
        rope_full=False, window=model["sliding_window"], qk_norm=True,
        moe=moe, layer_kinds=layer_kinds(model), **options)
    return lm, None
