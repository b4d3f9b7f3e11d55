"""Operations and bytes the ALGORITHM needs, from shapes and the harness's log.

Never from what the program says it did: a roofline or an mfu reads the
same work whoever implements the step. Recomputed operations (remat) do not
count. Every work function takes the cell (its configuration and traffic
files) and the harness's log of the traced window, and returns
{"flops": ..., "bytes": ...}.

A metric file names one by `work`, and `manifest.find("work", name)` finds
it: `work/<name>.py`'s function `<name>`, or one of those below (`__all__`).
The counts here are the dense block's (every head a KV head, every layer
full attention, a head of hidden / heads); a configuration they do not
describe brings work functions of its own, as files.
"""
from __future__ import annotations

__all__ = ["resnet_train_flops", "lora_train_flops", "flash_fwd_call",
           "flash_bwd_call", "paged_attention_traffic", "decode_flops"]


# ------------------------------------------------------------- ResNet-18-GN
def resnet_forward_flops(model: dict) -> tuple[float, float]:
    """(forward FLOPs of one sample, of which the stem): convolutions and
    the classifier, 2 per multiply-add; norms and activations are not
    counted. Mirrors the published ResNet-v1 basic-block layout with the
    CIFAR stem (3x3, stride 1, no pooling)."""
    hw = model["image_size"]
    cin, f = model["image_channels"], model["stem_filters"]
    conv = lambda hw_out, ci, co, k: 2.0 * hw_out * hw_out * co * ci * k * k
    stem = conv(hw, cin, f, 3)
    total, ci = stem, f
    for i, n_blocks in enumerate(model["stage_sizes"]):
        co = f * 2 ** i
        for j in range(n_blocks):
            if i > 0 and j == 0:
                hw //= 2
            total += conv(hw, ci, co, 3) + conv(hw, co, co, 3)
            if ci != co or (i > 0 and j == 0):
                total += conv(hw, ci, co, 1)
            ci = co
    total += 2.0 * ci * model["num_classes"]
    return total, stem


def resnet_train_flops(cell, log: dict) -> dict:
    """Forward + backward of every sample trained in the traced window:
    3x the forward, less the stem's input gradient, which nothing needs."""
    fwd, stem = resnet_forward_flops(cell.config["model"])
    return {"flops": (3.0 * fwd - stem) * log["samples"], "bytes": 0.0}


# ------------------------------------------------------------------- the LM
def lm_matmul_params(m: dict) -> float:
    """Weights that multiply every token: the blocks and the output head
    (the embedding is a lookup)."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    return (m["num_hidden_layers"] * (4.0 * d * d + 3.0 * d * ff)
            + d * m["vocab_size"])


def causal_attention_flops(m: dict, t: int) -> float:
    """QK^T and PV of ONE sequence of t tokens through every layer, the
    causal half counted once: 2 matmuls x 2 x t^2/2 x d a layer."""
    return m["num_hidden_layers"] * 2.0 * t * t * m["hidden_size"]


def lora_train_flops(cell, log: dict) -> dict:
    """Model FLOPs of federated LoRA on a frozen base, per token: forward
    2P, activation backward 2P (no weight gradient of the base), attention
    forward once and backward twice that, and the rank-r adapter gradients
    (three rank-r products per adapted kernel). No recompute."""
    m, tr = cell.config["model"], cell.traffic
    t, r = tr["seq_len"], tr["lora_rank"]
    d = m["hidden_size"]
    per_seq = (4.0 * lm_matmul_params(m) * t
               + 3.0 * causal_attention_flops(m, t)
               + m["num_hidden_layers"] * len(tr["lora_targets"])
               * 3.0 * 2.0 * (d * r + r * d) * t)
    return {"flops": per_seq * log["tokens"] / t, "bytes": 0.0}


def _flash_shape(cell):
    m, tr = cell.config["model"], cell.traffic
    h = m["num_attention_heads"]
    return tr["batch_size"], tr["seq_len"], h, m["hidden_size"] // h


def flash_fwd_call(cell, log: dict) -> dict:
    """One causal forward call at the cell's shapes: S = QK^T and PV, the
    causal half; reads q, k, v, writes o (bf16)."""
    b, t, h, dh = _flash_shape(cell)
    return {"flops": 2.0 * b * h * t * t * dh,
            "bytes": 4.0 * b * t * h * dh * 2}


def flash_bwd_call(cell, log: dict) -> dict:
    """One causal backward (dq and dkv kernels together): the least is five
    products (S again, dP, dQ, dK, dV); reads q, k, v, o, do, writes dq,
    dk, dv."""
    b, t, h, dh = _flash_shape(cell)
    return {"flops": 5.0 * b * h * t * t * dh,
            "bytes": 8.0 * b * t * h * dh * 2}


def paged_attention_traffic(cell, log: dict) -> dict:
    """What the TRAFFIC requires of the paged kernel: for every token
    emitted in the traced window, its request's context read once as K and
    once as V in every layer."""
    m = cell.config["model"]
    per_ctx_token = 2.0 * m["hidden_size"] * m["num_hidden_layers"]
    ctx = log["context_token_sum"]
    return {"flops": 2.0 * per_ctx_token * ctx,
            "bytes": per_ctx_token * 2 * ctx}


def decode_flops(cell, log: dict) -> dict:
    """2 x parameters x every token (prompt and generated) the engine
    processed in the traced window."""
    return {"flops": 2.0 * lm_matmul_params(cell.config["model"])
            * log["processed_tokens"], "bytes": 0.0}

