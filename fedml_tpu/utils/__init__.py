"""Shared runtime utilities."""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """`<checkout>/.jax_cache` — FIXED on purpose: the directory is part of
    jax's cache key, so a path that moves (tempfile, pid, timestamp) never
    hits."""
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """THE place the persistent XLA compilation cache is configured; every
    entry that builds a program calls it before its first trace (Simulator,
    CentralizedTrainer, DecodeEngine, start_replica, FedMLRunner, bench.py,
    chip_smoke.py), so a second process — a rerun, a resumed job, the next
    replica — loads compiled programs instead of rebuilding them.

    Where the directory lives is decided OUTSIDE the program when
    `JAX_COMPILATION_CACHE_DIR` is set: jax reads that variable itself and
    this function sets no directory in code. Otherwise the cache is
    `default_cache_dir()`. Either way every compile is cached (threshold
    0): the engine's programs compile in seconds each and there are four
    per bucket. Returns the directory in effect."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(default_cache_dir()))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
