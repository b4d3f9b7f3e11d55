"""What the TRAFFIC requires of the indexer's scores kernel in the traced
window: for every processed query that sees more than `index_topk` keys, one
product of every index head with every key it sees, in every layer (FLOPs),
and those keys' index rows read once (bytes): by a generated token its own
context, by a prefill chunk's queries shared a chunk at a time."""
from __future__ import annotations

from chipbench.work.glm5_decode_flops import key_flops, key_sums


def index_scores_traffic(cell, log: dict) -> dict:
    m = cell.config["model"]
    _, score = key_flops(m)
    keys = key_sums(log)
    layers = m["num_hidden_layers"]
    row = 2.0 * m.get("index_head_dim",
                      m["hidden_size"] // m["num_attention_heads"])
    chunk = cell.traffic.get("serve", {}).get("prefill_chunk") or 1
    decode = keys["decode_scored_key_sum"]
    return {"flops": layers * score * keys["scored_key_sum"],
            "bytes": layers * row * (
                decode + (keys["scored_key_sum"] - decode) / chunk)}
