"""What the TRAFFIC requires of the paged kernel in a block-diffusion
engine's traced window: a window's `block_length` queries of every head
share ONE read of their slot's context, K and V of the KV heads in every
layer (`block_context`: the positions each live slot's window attends,
summed over drained block frames), and score every key of it once a query
head and row. From shapes and the harness's log alone. (A configuration
that leaves the keys out reads as the dense block: every head a KV head of
hidden / heads, a window of one query; a log without `block_context` as
`paged_attention_traffic`'s `context_token_sum`.)"""
from __future__ import annotations


def block_attention_traffic(cell, log: dict) -> dict:
    m = cell.config["model"]
    layers, heads = m["num_hidden_layers"], m["num_attention_heads"]
    dh = m.get("head_dim", m["hidden_size"] // heads)
    ctx = log.get("block_context", log.get("context_token_sum", 0))
    rows = m.get("block_length", 1) * heads
    return {"flops": 4.0 * rows * dh * ctx * layers,
            "bytes": 2.0 * m.get("num_key_value_heads", heads) * dh * 2
            * ctx * layers}
