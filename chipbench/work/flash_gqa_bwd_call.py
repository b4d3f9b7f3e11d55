"""One full-causal backward of the grouped-head flash kernels (dq and dkv
together): five products over the causal half (S again, dP, dQ, dK, dV);
reads q, o, do and writes dq (query-sized), reads k, v and writes dk, dv
(KV-sized)."""
from __future__ import annotations

from chipbench.work.flash_gqa_fwd_call import sizes


def flash_gqa_bwd_call(cell, log: dict) -> dict:
    b, t, q_size, k_size, width, _ = sizes(cell)
    return {"flops": 5.0 * 2.0 * b * width * t * (t + 1) / 2.0,
            "bytes": (4.0 * q_size + 4.0 * k_size) * 2}
