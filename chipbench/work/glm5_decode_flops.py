"""Model FLOPs of what the `glm5_744b_ep16` replica processed in the traced
window, from the configuration's shapes and the harness's own log of the
traffic (`drivers/serve_docs.py` `docs_log`): every processed token (prefilled
or generated) through the weights that multiply it, its attention over the
keys it SELECTS, and the indexer's scores over the keys it sees where it sees
more than `index_topk`. The held experts count by the even share of a token's
pairs that lands here (`num_experts_per_tok` x held / `router_num_experts`):
what the traffic asks for, not what this seed's router did. (A configuration
that leaves the latent keys out reads as the dense block: four d x d
projections, every key attended by heads of hidden / heads and scored by
those same heads, a SwiGLU in every layer.)"""
from __future__ import annotations


def token_params(m: dict) -> float:
    """Weights that multiply one token, the absent experts' left out."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    n = m["num_hidden_layers"]
    attention, indexer = 4.0 * d * d, 0.0
    if "kv_lora_rank" in m:
        qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
        nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                          m["v_head_dim"])
        attention = (d * qr + qr * h * (nope + rope) + d * (kr + rope)
                     + kr * h * (nope + vd) + h * vd * d)
        indexer = (qr * m["index_n_heads"] * m["index_head_dim"]
                   + d * m["index_head_dim"] + d * m["index_n_heads"])
    dense = m.get("first_k_dense_replace", n)
    sparse = 0.0
    if dense < n:
        expert = 3.0 * d * m["moe_intermediate_size"]
        sparse = (m["n_shared_experts"] * expert + d * m["router_num_experts"]
                  + expert * m["num_experts_per_tok"] * m["n_routed_experts"]
                  / m["router_num_experts"])
    return (n * (attention + indexer) + dense * 3.0 * d * m["intermediate_size"]
            + (n - dense) * sparse + d * m["vocab_size"])


def key_flops(m: dict) -> tuple[float, float]:
    """(FLOPs a selected key costs a query, FLOPs a scored key costs it), a
    layer: the absorbed score and weighted sum over `kv_lora_rank` (+ rope)
    for every head; the indexer's product for every index head."""
    h = m["num_attention_heads"]
    head = m["hidden_size"] // h
    attend = 2.0 * h * (2 * m.get("kv_lora_rank", head)
                        + m.get("qk_rope_head_dim", 0))
    score = 2.0 * m.get("index_n_heads", h) * m.get("index_head_dim", head)
    return attend, score


def key_sums(log: dict) -> dict:
    """The log's four sums of keys; a log without them (a mix with no
    selection) attends and scores every key of `context_token_sum`."""
    seen = log.get("context_token_sum", 0)
    return {k: log.get(k, seen) for k in (
        "selected_key_sum", "scored_key_sum", "decode_selected_key_sum",
        "decode_scored_key_sum")}


def glm5_decode_flops(cell, log: dict) -> dict:
    m = cell.config["model"]
    attend, score = key_flops(m)
    keys = key_sums(log)
    return {"flops": 2.0 * token_params(m) * log["processed_tokens"]
            + m["num_hidden_layers"] * (attend * keys["selected_key_sum"]
                                        + score * keys["scored_key_sum"]),
            "bytes": 0.0}
