"""Model FLOPs of federated LoRA on the `exaone_moe` block (configs/
kexaone_236b_ep8.json), of the tokens trained in the traced window. Per
token, forward and activation backward (the base is frozen: no weight
gradient but the adapters'): the grouped-head projections (wq and wo of heads
x head_dim, wk and wv of KV heads x head_dim), layer 0's dense SwiGLU, in
every sparse layer the router and the shared expert, the output head over
the vocabulary slice, and the rank-r adapter gradients. The held experts by
what was ROUTED here: `moe_pairs` of the log (the program's own count of
token-expert pairs, summed over the traced rounds), three d x f products a
pair. Scores once forward and twice backward: the causal half in a full
layer, the band of `sliding_window` keys in a window layer. No recompute."""
from __future__ import annotations


def shape(cell):
    """(heads, KV heads, head_dim, window, [layer is windowed], [layer is
    sparse]) of the layers the configuration keeps. A key the configuration
    leaves out reads as the dense block's: as many KV heads as heads, heads
    of hidden / heads, every layer full attention over a dense SwiGLU."""
    m = cell.config["model"]
    n, heads = m["num_hidden_layers"], m["num_attention_heads"]
    return (heads, m.get("num_key_value_heads") or heads,
            m.get("head_dim") or m["hidden_size"] // heads,
            m.get("sliding_window"),
            [k == "sliding_attention" for k in m.get("layer_types", [])[:n]]
            or [False] * n,
            [k == "sparse" for k in m.get("mlp_layer_types", [])[:n]]
            or [False] * n)


def band_pairs(t: int, window: int) -> float:
    """(query, key) pairs a window layer scores in one sequence of t (no
    window: the causal half)."""
    w = min(window or t, t)
    return t * w - w * (w - 1) / 2.0


def kexaone_train_flops(cell, log: dict) -> dict:
    m, tr = cell.config["model"], cell.traffic
    heads, kv, dh, window, windowed, sparse = shape(cell)
    d, t, r = m["hidden_size"], tr["seq_len"], tr["lora_rank"]
    f = m.get("moe_intermediate_size", 0)
    wide = {"wq": (d, heads * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
            "wo": (heads * dh, d)}
    per_token = d * m["vocab_size"]
    for is_sparse in sparse:
        per_token += sum(a * b for a, b in wide.values())
        per_token += (d * m["router_num_experts"]
                      + 3.0 * d * f * m["num_shared_experts"] if is_sparse
                      else 3.0 * d * m["intermediate_size"])
    adapters = len(sparse) * sum(3.0 * 2.0 * r * sum(wide[w])
                                 for w in tr["lora_targets"])
    scores = sum(2.0 * 2.0 * heads * dh * (
        band_pairs(t, window) if w else t * (t + 1) / 2.0) for w in windowed)
    per_seq = 4.0 * per_token * t + adapters * t + 3.0 * scores
    return {"flops": per_seq * log["tokens"] / t
            + 2.0 * 6.0 * d * f * log.get("moe_pairs", 0.0), "bytes": 0.0}
