"""Model serving — predictors + HTTP inference runner.

(reference: python/fedml/serving/ — 1,990 LoC: FedMLPredictor ABC,
FedMLInferenceRunner FastAPI app, fedml_server.py reusing cross-silo init
for federated serving.)

Layer map position: L3 runtime (SURVEY.md §1). The compute path is a jitted
bucketed forward (serving/predictor.py); LLM requests can opt into the
continuous-batching slot engine (serving/engine.py — one persistent donated
KV cache, concurrent requests share device steps); the HTTP surface mirrors
the reference's /predict + /ready contract (serving/inference_runner.py).
`serve_simulator` is the federated-serving bridge: serve the global model a
Simulator trained (or a checkpoint directory it saved).
"""
from __future__ import annotations

import importlib
from typing import Callable

__all__ = [
    "Predictor", "JaxPredictor", "GreedyLMPredictor",
    "DecodeEngine", "Ticket", "lm_predictor_from_config",
    "FedMLInferenceRunner", "DEFAULT_PORT", "serve_simulator",
    "predictor_from_checkpoint", "predictor_from_artifact",
    "export_model", "load_export", "predictor_from_export",
]

# Lazy re-exports (PEP 562, same pattern as the package root): the heavy
# submodules import jax, but `fedml_tpu.serving.knobs` — the serve-knob
# registry config.py validates against at load time — must be importable
# without dragging a backend in. Importing THIS package therefore stays
# jax-free; the first access to an engine/predictor symbol pays the
# submodule import.
_LAZY = {
    "DecodeEngine": "engine", "Ticket": "engine",
    "export_model": "export", "load_export": "export",
    "predictor_from_export": "export",
    "DEFAULT_PORT": "inference_runner",
    "FedMLInferenceRunner": "inference_runner",
    "GreedyLMPredictor": "predictor", "JaxPredictor": "predictor",
    "Predictor": "predictor",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    return getattr(importlib.import_module(f".{mod}", __name__), name)


def lm_predictor_from_config(cfg, model, params, adapters=None,
                             detokenize=None) -> "GreedyLMPredictor":
    """Build the LM serving predictor from a Config's `serve_args` section
    (YAML key `serve_args`, alias `serve` — validated at load,
    config.py): `decode_slots` > 0 starts the continuous-batching engine,
    `engine_max_len`/`engine_eos_id`/`engine_fetch_chunk`/
    `sampler_cache_size`/`kv_cache` tune it; `kv_page_size` (default 16)
    is the page of its KV pool, with `kv_n_pages`/`prefill_chunk`/
    `prefix_cache` (engine module docstring). This is the config-side
    consumer of cfg.serve_args; the deploy path (scheduler.start_replica)
    feeds the serve-spec dict through the SAME knob mapping
    (predictor.lm_predictor_from_serve_knobs)."""
    from .predictor import lm_predictor_from_serve_knobs

    return lm_predictor_from_serve_knobs(
        cfg.serve_args.extra, model, params, adapters=adapters,
        detokenize=detokenize)


def predictor_from_artifact(store, round_idx: int,
                            apply_fn: Callable) -> "JaxPredictor":
    """Serve the round-N aggregated model published through the mlops
    artifact path (reference shape: serving loads the S3 model the
    aggregator uploaded with log_aggregated_model_info — core/mlops/
    __init__.py:388). `store` is a utils/artifacts.py store (or anything
    with .get(name))."""
    from ..utils.artifacts import aggregated_name
    from .predictor import JaxPredictor

    return JaxPredictor(apply_fn, store.get(aggregated_name(round_idx)))


def predictor_from_checkpoint(ckpt_dir: str, apply_fn: Callable,
                              server_template) -> JaxPredictor:
    """Load the latest orbax checkpoint's global model and wrap it as a
    predictor (reference analog: fedml_server.py serving the aggregated
    model; here the source of truth is utils/checkpoint.py state)."""
    from ..utils.checkpoint import restore_checkpoint
    from .predictor import JaxPredictor

    _r, server, _c, _h, _hist = restore_checkpoint(ckpt_dir, server_template)
    return JaxPredictor(apply_fn, server.params)


def serve_simulator(sim, host: str = "127.0.0.1", port: int = 0,
                    background: bool = True) -> FedMLInferenceRunner:
    """Serve a (trained) Simulator's global model over HTTP. Params are
    copied: the round engine donates its server state, so serving by
    reference would break if training continues after this call."""
    import jax
    import jax.numpy as jnp

    from .inference_runner import FedMLInferenceRunner
    from .predictor import JaxPredictor

    pred = JaxPredictor(
        sim.apply_fn, jax.tree.map(jnp.array, sim.server_state.params))
    runner = FedMLInferenceRunner(pred, host=host, port=port)
    if background:
        runner.start()
    else:
        runner.run()
    return runner
