"""What the held experts' grouped products need in the traced window,
whatever implements them: for every token-expert pair routed here
(`moe_pairs` of the log) three d x f products, 6 d f FLOPs forward, in each
pass the step makes over the layer (forward, the backward to the rows, and
with remat the forward again; the frozen experts take no weight gradient);
and the held experts' weights read once a pass by every sequence in every
sparse layer, beside the pairs' rows in and out. (A configuration without
experts reads as the dense block: one expert a layer, of the dense width,
that every token is routed to.)"""
from __future__ import annotations


def expert_matmul_work(cell, log: dict) -> dict:
    m, tr = cell.config["model"], cell.traffic
    n = m["num_hidden_layers"]
    d = m["hidden_size"]
    f = m.get("moe_intermediate_size", m["intermediate_size"])
    kinds = m.get("mlp_layer_types", ["sparse"] * n)[:n]
    layers = sum(k == "sparse" for k in kinds)
    pairs = log.get("moe_pairs", log["tokens"] * layers)
    passes = 3.0 if tr.get("remat") else 2.0
    calls = log["tokens"] / tr["seq_len"] * layers
    weights = 3.0 * m.get("num_experts", 1) * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return {"flops": passes * 6.0 * d * f * pairs,
            "bytes": passes * (calls * weights + rows)}
