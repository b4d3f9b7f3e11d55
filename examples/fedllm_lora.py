"""FedLLM: federated LoRA fine-tuning of a transformer (the reference
spotlight project, python/spotlight_prj/fedllm/ — peft LoRA over cross-silo;
here adapters federate through the standard round engine, and the
long-context variant shards sequences over a `seq` mesh axis with ring
attention).

Run:  python examples/fedllm_lora.py              (flat; any device count)
      python examples/fedllm_lora.py --ring       (needs >= 8 devices, e.g.
          XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU)
      python examples/fedllm_lora.py --int8       (QLoRA shape: int8 frozen
          base, per-layer dequant inside the layer scan — the 7B layout)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.config import TrainArgs
from fedml_tpu.core.algorithm import ServerState
from fedml_tpu.llm import (
    TransformerLM, count_params, federated_lora, make_fedllm_seq_round,
    shard_fedllm_data,
)
from fedml_tpu.parallel.mesh import make_mesh
from fedml_tpu.parallel.round import build_round_fn

VOCAB, T, HEADS = 64, 32, 4
model = TransformerLM(vocab_size=VOCAB, d_model=64, n_layers=2,
                      n_heads=HEADS, d_ff=128)
base = model.init(jax.random.key(0), jnp.zeros((1, T), jnp.int32))["params"]
t = TrainArgs(epochs=1, batch_size=8, learning_rate=0.5)

rs = np.random.RandomState(0)
n_clients = 4
seqs = (rs.randint(0, VOCAB, (n_clients, 16, 1)) + np.arange(T + 1)) % VOCAB
data = {"x": seqs[:, :, :-1].astype(np.int32),
        "y": seqs[:, :, 1:].astype(np.int32),
        "mask": np.ones((n_clients, 16), np.float32)}
ids = jnp.arange(n_clients)
weights = jnp.full((n_clients,), 16.0)

if "--ring" in sys.argv:
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=8)
    mesh = make_mesh({"silos": 2, "seq": 4})
    rnd = make_fedllm_seq_round(model, base, t, mesh)
    st = ServerState(adapters, None, jnp.int32(0), None)
    hdata = shard_fedllm_data({k: v[:2] for k, v in data.items()}, mesh)
    for r in range(8):
        st, m = rnd(st, base, hdata, jnp.arange(2), weights[:2],
                    jax.random.fold_in(jax.random.key(2), r))
        print(f"ring round {r}: loss={float(m['train_loss']):.3f}")
    sys.exit(0)

if "--int8" in sys.argv:
    # QLoRA shape: int8 frozen base dequantized per layer INSIDE the layer
    # scan (the full-7B single-chip layout — llm/quant.py)
    from fedml_tpu.algorithms.builtin import make_fedavg
    from fedml_tpu.llm.lora import lora_init
    from fedml_tpu.llm.quant import (
        make_inscan_quant_apply, quant_bytes, quantize_tree_int8,
    )

    model = TransformerLM(vocab_size=VOCAB, d_model=64, n_layers=2,
                          n_heads=HEADS, d_ff=128, scan_layers=True)
    base = model.init(jax.random.key(0),
                      jnp.zeros((1, T), jnp.int32))["params"]
    qbase = quantize_tree_int8(base)
    print(f"int8 base: {quant_bytes(qbase):,} bytes "
          f"(vs {4 * count_params(base):,} f32)")
    inscan = make_inscan_quant_apply(HEADS, dtype=jnp.float32)
    alg = make_fedavg(
        lambda variables, x: inscan(qbase, variables["params"], x), t)
    adapters = lora_init(jax.random.key(1), base, rank=8)
    label = "int8 round"
else:
    alg, adapters = federated_lora(model, base, t, jax.random.key(1), rank=8)
    label = "round"

print(f"adapter payload: {count_params(adapters):,} params "
      f"({count_params(adapters) / count_params(base):.2%} of base)")
rnd = build_round_fn(alg, mesh=None)
st = alg.server_init(adapters, None)
for r in range(8):
    out = rnd(st, jnp.zeros((n_clients,)),
              {k: jnp.asarray(v) for k, v in data.items()},
              ids, weights, jax.random.fold_in(jax.random.key(2), r), None)
    st = out.server_state
    print(f"{label} {r}: loss={float(out.metrics['train_loss']):.3f}")

if "--int8" in sys.argv:
    # serve the federated result DIRECTLY in its QLoRA layout: int8 frozen
    # base + the trained adapters, KV-cache decode, greedy then sampled
    # (serving/predictor.py + llm/decode.py)
    from fedml_tpu.serving import GreedyLMPredictor

    pred = GreedyLMPredictor(model, qbase, max_len=64, kv_cache=True,
                             adapters=st.params)
    prompt = seqs[0, 0, :8].astype(int).tolist()
    greedy = pred.predict({"tokens": prompt, "max_new_tokens": 8})
    sampled = pred.predict({"tokens": prompt, "max_new_tokens": 8,
                            "temperature": 0.8, "top_k": 8, "seed": 0})
    print("served greedy:", greedy["generated_tokens"])
    print("served sampled:", sampled["generated_tokens"])
    assert len(greedy["generated_tokens"]) == 8
print("OK fedllm lora")
