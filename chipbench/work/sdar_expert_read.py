"""What the experts' grouped products need in the traced window of a block
program, whatever implements them: the weights of every LIVE expert (one
with at least a row routed to it, a layer, a forward: the engine's
`moe_experts_live`, summed over layers and drained block frames) read once,
three `hidden x width` matrices in bfloat16, beside the pairs' rows in and
out; and for every (position, expert) pair routed (`moe_pairs`) three
products, 6 d f FLOPs. From shapes and the harness's log alone. (A
configuration without experts and a log without the two counters read as
the dense block: one expert a layer, of the dense width, that every token
is routed to, as `expert_matmul_work` has it.)"""
from __future__ import annotations


def sdar_expert_read(cell, log: dict) -> dict:
    m = cell.config["model"]
    d = m["hidden_size"]
    f = m.get("moe_intermediate_size", m["intermediate_size"])
    dense = log.get("tokens", 0) * m["num_hidden_layers"]
    pairs = log.get("moe_pairs", dense)
    live = log.get("moe_experts_live", dense)
    return {"flops": 6.0 * d * f * pairs,
            "bytes": live * 3.0 * d * f * 2 + pairs * (2 * d + 3 * f) * 2}
