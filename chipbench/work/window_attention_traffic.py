"""What the TRAFFIC requires of the window kernel (`paged_attention_window`,
a window layer's decode step over its ring of pages) in a traced window: for
every token a decode step emitted, its query's keys (its context, at most the
window: `window_step_keys`, from the harness's request log) read once as K
and once as V of the KV heads, and scored by every query head, in every
window layer. A prefill chunk's window layers gather their ring and run no
such kernel, so their keys are not counted here. From shapes and the
harness's log alone. (A configuration without `sliding_window_layout` reads
as the dense block: every layer's every emitted token's context, as
`paged_attention_traffic`'s `context_token_sum`.)"""
from __future__ import annotations


def window_attention_traffic(cell, log: dict) -> dict:
    m = cell.config["model"]
    layers = sum(m.get("sliding_window_layout",
                       [1] * m["num_hidden_layers"]))
    heads = m["num_attention_heads"]
    dh = m.get("head_dim", m["hidden_size"] // heads)
    kv = m.get("num_key_value_heads", heads)
    keys = log.get("window_step_keys",
                   log.get("context_token_sum", 0)) * layers
    return {"flops": 4.0 * heads * dh * keys,
            "bytes": 2.0 * kv * dh * 2 * keys}
