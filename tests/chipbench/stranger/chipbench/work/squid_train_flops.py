"""Model FLOPs of federated LoRA on the `squid` block, per token: the
matmuls that multiply every token forward and activation-backward (wq and wo
of heads x head, wk and wv of KV heads x head, the gated MLP's three, the
output head), causal attention once forward and twice backward over every
QUERY head, and the rank-r adapter gradients."""
from __future__ import annotations


def squid_train_flops(cell, log: dict) -> dict:
    m, tr = cell.config["model"], cell.traffic
    d, t, r = m["width"], tr["seq_len"], tr["lora_rank"]
    q, kv = (m[k] * m["head_width"] for k in ("query_heads", "kv_heads"))
    layers = m["num_hidden_layers"]
    params = layers * (2.0 * d * (q + kv) + 3.0 * d * m["ffn_width"]) \
        + d * m["vocab_size"]
    outs = {"wq": q, "wk": kv, "wv": kv, "wo": d}
    ins = {"wq": d, "wk": d, "wv": d, "wo": q}
    adapters = sum(3.0 * 2.0 * r * (ins[w] + outs[w])
                   for w in tr["lora_targets"])
    per_seq = (4.0 * params * t + 3.0 * layers * 2.0 * t * t * q
               + layers * adapters * t)
    return {"flops": per_seq * log["tokens"] / t, "bytes": 0.0}
