"""`model_type: smallthinker`: SmallThinker's decoder on
`fedml_tpu.llm.TransformerLM`: grouped KV heads of `head_dim`, no q/k norm,
a layer of `sliding_window_layout` 1 a "window" layer (the last
`sliding_window_size` positions, rotary positions) and of 0 a "full" layer
without rotary positions (`rope_full` False: `rope_layout` must coincide),
every layer an expert layer (a softmax router over all
`moe_num_primary_experts` that reads the attention norm's output, top
`moe_num_active_primary_experts`, renormalised, ReGLU experts of
`moe_ffn_hidden_size`, no shared expert, all of them held here).
configs/smallthinker_21b_pp7.json says what the config leaves open. The
layers stay unrolled: an expert layer's weights are read where they lie.

`build` returns the module and the model part of `start_replica`'s spec: the
`lm` recipe carries the same fields the module was made from, as plain data."""
from __future__ import annotations


def recipe(model: dict) -> dict:
    """The `lm` recipe `serving.scheduler.start_replica` builds the model
    from (TransformerLM's fields, `moe` as its dataclass's)."""
    window, rope = model["sliding_window_layout"], model["rope_layout"]
    if list(window) != list(rope) or model["rope_scaling"] is not None:
        raise ValueError("window layers are the rotated ones and full layers "
                         "the unrotated (rope_layout == sliding_window_layout"
                         "), rotary positions unscaled (rope_scaling null)")
    if not model["moe_primary_router_apply_softmax"]:
        raise ValueError("the router is a softmax over every expert "
                         "(moe_primary_router_apply_softmax true)")
    n = model["num_hidden_layers"]
    if len(window) != n:
        raise ValueError(f"sliding_window_layout gives {len(window)} layers "
                         f"of {n}")
    return {
        "vocab_size": model["vocab_size"], "d_model": model["hidden_size"],
        "n_layers": n, "n_heads": model["num_attention_heads"],
        "d_ff": model["moe_ffn_hidden_size"],
        "n_kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"], "norm_eps": model["rms_norm_eps"],
        "rope_base": float(model["rope_theta"]),
        "window": model["sliding_window_size"], "rope_full": False,
        "moe": {
            "n_experts": model["moe_num_primary_experts"],
            "top_k": model["moe_num_active_primary_experts"],
            "d_expert": model["moe_ffn_hidden_size"],
            "n_shared": 0, "scoring": "softmax",
            "norm_topk": model["norm_topk_prob"],
            "act": "reglu", "route_from": "attn"},
        "layer_kinds": [["window" if w else "full", "moe"] for w in window]}


def build(model: dict, **options):
    from fedml_tpu.llm.moe import MoE
    from fedml_tpu.llm.transformer import TransformerLM

    lm = recipe(model)
    module = TransformerLM(
        **{k: v for k, v in lm.items() if k not in ("moe", "layer_kinds")},
        moe=MoE(**lm["moe"]),
        layer_kinds=tuple(tuple(k) for k in lm["layer_kinds"]), **options)
    return module, {"model_kind": "lm", "lm": lm}
