"""One backward of the windowed flash kernels (dq and dkv together): five
products over the band; bytes as the full backward's."""
from __future__ import annotations

from chipbench.work.flash_gqa_fwd_call import sizes
from chipbench.work.kexaone_train_flops import band_pairs


def flash_window_bwd_call(cell, log: dict) -> dict:
    b, t, q_size, k_size, width, window = sizes(cell)
    return {"flops": 5.0 * 2.0 * b * width * band_pairs(t, window),
            "bytes": (4.0 * q_size + 4.0 * k_size) * 2}
