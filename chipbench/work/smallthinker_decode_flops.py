"""Model FLOPs of what the `smallthinker_21b_pp7` replica processed in the
traced window, from the configuration's shapes and the engine's counters in
the harness's log (`drivers/serve_routed.py`): every position a decode step
forwarded (`slot_steps`: the live slots of the drained steps) or a prefill
chunk admitted (`prompt_tokens`) through the attention projections, the
router and its `moe_num_active_primary_experts` chosen experts (three
products each, ReGLU) in every layer; the head over every position a step
forwarded and each admitted request's last; and the scores and weighted
sums over the keys each query attends: every key it sees in a global layer
(`context_keys`), at most the window in a window layer (`window_keys`).
(A configuration that leaves the keys out reads as the dense block: every
head a KV head of hidden / heads, one expert of the dense width that every
position takes; a log without the counters as `decode_flops`'s
`processed_tokens` and `context_token_sum`.)"""
from __future__ import annotations


def smallthinker_decode_flops(cell, log: dict) -> dict:
    m = cell.config["model"]
    d, heads = m["hidden_size"], m["num_attention_heads"]
    dh = m.get("head_dim", d // heads)
    kv = m.get("num_key_value_heads", heads)
    layers = m["num_hidden_layers"]
    windowed = sum(m.get("sliding_window_layout", ()))
    per_position = (2.0 * d * heads * dh + 2.0 * d * kv * dh
                    + d * m.get("moe_num_primary_experts", 0)
                    + m.get("moe_num_active_primary_experts", 1) * 3.0 * d
                    * m.get("moe_ffn_hidden_size", m.get("intermediate_size",
                                                         4 * d)))
    stepped = log.get("slot_steps", log.get("emitted_tokens", 0))
    positions = stepped + log.get("prompt_tokens",
                                  log.get("processed_tokens", 0) - stepped)
    seen = log.get("context_keys", log.get("context_token_sum", 0))
    keys = (layers - windowed) * seen + windowed * log.get("window_keys",
                                                           seen)
    return {"flops": 2.0 * layers * per_position * positions
            + 2.0 * d * m["vocab_size"] * (stepped + log.get("admitted", 0))
            + 4.0 * heads * dh * keys, "bytes": 0.0}
