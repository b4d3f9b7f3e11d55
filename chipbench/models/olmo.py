"""`model_type: olmo`: `fedml_tpu.llm.TransformerLM`, the one dense decoder
block the program has (full multi-head attention, heads of hidden / heads,
SwiGLU). configs/olmo1b.json lists where that block departs from OLMo.

A model builder is `build(model, **options) -> (module, spec)`: from the
configuration's `model` group (and what the path decides and no
configuration states: `attn_fn`, `remat`) to the flax module a training
driver drives and the model part of the spec `start_replica` takes. The
harness makes the weights from --seed over the module's shapes; the plain
reference is independent of this file (`reference/<config>.py`)."""
from __future__ import annotations


def build(model: dict, **options):
    from fedml_tpu.llm.transformer import TransformerLM

    h = model["num_attention_heads"]
    kv = model.get("num_key_value_heads") or h
    dh = model.get("head_dim") or model["hidden_size"] // h
    if kv != h or dh * h != model["hidden_size"]:
        raise ValueError(
            f"TransformerLM has {h} heads of hidden/heads and as many KV "
            f"heads; the configuration asks for {kv} KV heads of {dh}: it "
            "needs a model builder of its own")
    lm = {"vocab_size": model["vocab_size"], "d_model": model["hidden_size"],
          "n_layers": model["num_hidden_layers"], "n_heads": h,
          "d_ff": model["intermediate_size"], "scan_layers": True}
    return TransformerLM(**lm, **options), {"model_kind": "lm", "lm": lm}
