"""`model_type: sdar_moe`: SDAR's decoder (Qwen3-MoE's block under a
block-causal mask) on `fedml_tpu.llm.TransformerLM`: grouped KV heads of
`head_dim`, an RMSNorm over each head of q and k, rotary positions on every
layer, every layer an expert layer (a softmax router over all `num_experts`,
top `num_experts_per_tok`, renormalised, no shared expert, all of them held
here), and generation by diffusion over blocks of `block_length` from
`mask_token_id`. configs/sdar_30b_a3b_pp8.json says what the config leaves
open. The layers stay unrolled: an expert layer's weights are read where
they lie.

`build` returns the module and the model part of `start_replica`'s spec: the
`lm` recipe carries the same fields the module was made from, as plain data."""
from __future__ import annotations


def recipe(model: dict) -> dict:
    """The `lm` recipe `serving.scheduler.start_replica` builds the model
    from (TransformerLM's fields, `moe` as its dataclass's)."""
    if (model["decoder_sparse_step"], model["mlp_only_layers"],
            model["use_sliding_window"], model["rope_scaling"]) != (
            1, [], False, None):
        raise ValueError("every layer is an expert layer under full "
                         "attention with unscaled rotary positions "
                         "(decoder_sparse_step 1, mlp_only_layers [], "
                         "use_sliding_window false, rope_scaling null)")
    n = model["num_hidden_layers"]
    return {
        "vocab_size": model["vocab_size"], "d_model": model["hidden_size"],
        "n_layers": n, "n_heads": model["num_attention_heads"],
        "d_ff": model["intermediate_size"],
        "n_kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"], "qk_norm": True,
        "norm_eps": model["rms_norm_eps"],
        "rope_base": float(model["rope_theta"]),
        "diffusion_block": model["block_length"],
        "mask_id": model["mask_token_id"],
        "moe": {
            "n_experts": model["num_experts"],
            "top_k": model["num_experts_per_tok"],
            "d_expert": model["moe_intermediate_size"],
            "n_shared": 0, "scoring": "softmax",
            "norm_topk": model["norm_topk_prob"]},
        "layer_kinds": [["full", "moe"]] * n}


def build(model: dict, **options):
    from fedml_tpu.llm.moe import MoE
    from fedml_tpu.llm.transformer import TransformerLM

    lm = recipe(model)
    module = TransformerLM(
        **{k: v for k, v in lm.items() if k not in ("moe", "layer_kinds")},
        moe=MoE(**lm["moe"]),
        layer_kinds=tuple(tuple(k) for k in lm["layer_kinds"]), **options)
    return module, {"model_kind": "lm", "lm": lm}
