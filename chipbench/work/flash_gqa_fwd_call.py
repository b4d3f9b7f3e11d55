"""One full-causal forward call of the grouped-head flash kernel at the
cell's shapes: S = QK^T and PV over the causal half for every QUERY head;
reads q, writes o (heads x head_dim) and reads k, v (KV heads x head_dim),
bf16."""
from __future__ import annotations

from chipbench.work.kexaone_train_flops import shape


def sizes(cell):
    """(batch, tokens, elements of a q-sized and of a k-sized array, heads x
    head_dim, window) of one call."""
    heads, kv, dh, window, _w, _s = shape(cell)
    b, t = cell.traffic["batch_size"], cell.traffic["seq_len"]
    return b, t, b * t * heads * dh, b * t * kv * dh, heads * dh, window


def flash_gqa_fwd_call(cell, log: dict) -> dict:
    b, t, q_size, k_size, width, _ = sizes(cell)
    return {"flops": 2.0 * 2.0 * b * width * t * (t + 1) / 2.0,
            "bytes": (2.0 * q_size + 2.0 * k_size) * 2}
