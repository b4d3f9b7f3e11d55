"""What the TRAFFIC requires of the latent attention kernel in the traced
window: for every processed query the keys it selects, in every layer: the
absorbed score and weighted sum for every head (FLOPs), and the selected
rows `c_kv || k_rope` read once (bytes): by a generated token its own
selection, by a prefill chunk's queries their keys shared a chunk at a time.
A kernel that reads every live row where 2,048 are selected shows as a low
share."""
from __future__ import annotations

from chipbench.work.glm5_decode_flops import key_flops, key_sums


def latent_attention_traffic(cell, log: dict) -> dict:
    m = cell.config["model"]
    attend, _ = key_flops(m)
    keys = key_sums(log)
    layers = m["num_hidden_layers"]
    row = 2.0 * (m.get("kv_lora_rank", m["hidden_size"])
                 + m.get("qk_rope_head_dim", 0))
    chunk = cell.traffic.get("serve", {}).get("prefill_chunk") or 1
    decode = keys["decode_selected_key_sum"]
    return {"flops": layers * attend * keys["selected_key_sum"],
            "bytes": layers * row * (
                decode + (keys["selected_key_sum"] - decode) / chunk)}
