"""Model FLOPs of what the `sdar_30b_a3b_pp8` replica processed in the traced
window, from the configuration's shapes and the engine's counters in the
harness's log (`drivers/serve_blocks.py`): every position forwarded by a
block program (`block_positions`: live slots x block length a forward,
commits included) or prefilled (`prompt_tokens`) through the attention
projections, the router and its `num_experts_per_tok` chosen experts in
every layer; the head over every forwarded position (a block's logits are
over each position's own token; admission reads none); and the scores and
weighted sums over the keys each query sees (`context_keys`). (A
configuration that leaves the keys out reads as the dense block: every head
a KV head of hidden / heads, one expert of the dense width that every
position takes; a log without the counters as `decode_flops`'s
`processed_tokens` and `context_token_sum`.)"""
from __future__ import annotations


def position_params(m: dict) -> float:
    """Weights that multiply one position, a layer: q, k, v and o
    projections, the router, the chosen experts."""
    d, heads = m["hidden_size"], m["num_attention_heads"]
    dh = m.get("head_dim", d // heads)
    kv = m.get("num_key_value_heads", heads)
    return (2.0 * d * heads * dh + 2.0 * d * kv * dh
            + d * m.get("num_experts", 0) + m.get("num_experts_per_tok", 1)
            * 3.0 * d * m.get("moe_intermediate_size",
                              m["intermediate_size"]))


def sdar_decode_flops(cell, log: dict) -> dict:
    m = cell.config["model"]
    layers = m["num_hidden_layers"]
    forwarded = log.get("block_positions", log.get("processed_tokens", 0))
    positions = forwarded + log.get("prompt_tokens", 0)
    keys = 4.0 * m["num_attention_heads"] * m.get(
        "head_dim", m["hidden_size"] // m["num_attention_heads"]) * log.get(
            "context_keys", log.get("context_token_sum", 0))
    return {"flops": 2.0 * layers * position_params(m) * positions
            + 2.0 * m["hidden_size"] * m["vocab_size"] * forwarded
            + layers * keys, "bytes": 0.0}
