"""One forward call of the windowed flash kernel at the cell's shapes: the
two products over the BAND (each query and the `sliding_window` keys it
sees), whatever blocks the kernel walks to cover it; bytes as the full
call's."""
from __future__ import annotations

from chipbench.work.flash_gqa_fwd_call import sizes
from chipbench.work.kexaone_train_flops import band_pairs


def flash_window_fwd_call(cell, log: dict) -> dict:
    b, t, q_size, k_size, width, window = sizes(cell)
    return {"flops": 2.0 * 2.0 * b * width * band_pairs(t, window),
            "bytes": (2.0 * q_size + 2.0 * k_size) * 2}
