"""XLA cost/memory ledger — what each compiled program costs and holds.

The metrics plane (ISSUE 2) counts compiles and retraces; this module
(ISSUE 17 leg a) attributes them: every `track_jit`-wrapped entry point
(round/block/chunk/finalize/eval, the serving engine's admit/step/spec
programs) reports its program's `cost_analysis()` FLOPs and
bytes-accessed plus its HBM argument/output footprint, published as
`xla.program.*` gauges keyed by program name. Capture is AOT and, where
the backend allows it, COMPILE-FREE: on a compile-cache growth the wrapper
hands this module the call's abstract signature (ShapeDtypeStructs —
donated buffers are never touched), `jitted.lower(...)` answers
`cost_analysis()` from the lowering (milliseconds, no XLA optimization
pass; XLA:TPU answers only for a compiled program, so there the capture
compiles — a load from the persistent cache the entry points keep on), and
argument/output bytes come from the avals; steady-state calls pay one
counter bump. The deeper
`memory_analysis()` stats (temp + generated-code bytes) require a real
compile — a full DUPLICATE of XLA's optimization work per program, which
once cost tier-1 ~50% extra on engine-heavy modules — so they ride only
under `FEDML_TPU_XLA_DEEP=1` (hbm_peak then includes temps; the default
ledger's hbm_peak = args + out is a documented lower bound).

Two more ledgers ride along:
- `register_buffers(kind, tree)` — the DEVICE-MEMORY ledger: resident
  pytrees (params, donated carries, the paged KV pool) summed by nbytes
  into `xla.ledger.<kind>_bytes` gauges + the `xla.ledger.device_bytes`
  total. The engine's KV pool entry must agree with its own
  `serving.kv_bytes_per_slot` math within 1% (pinned in tests).
- `measured_mfu()` — utilization from MEASURED wall time (the recorder's
  span totals) over cost-analysis FLOPs, superseding `utils/flops.py`
  hand estimates wherever a compiled program exists. Achieved FLOP/s is
  always published (`xla.program.flops_per_s.*`); the MFU ratio
  (`xla.program.mfu.*`) only where a spec peak is known — on the CPU
  interpret lanes `tpu_spec_peak_tflops` is None and no MFU is claimed.

A failed capture never takes a training step down: it logs a WARNING and
leaves the program out of `programs()` — which is what chip_smoke.py
checks, so the gap is seen on the chip instead of reading as a zero.
`set_enabled(False)` is the bench overhead row's off-switch.
"""
from __future__ import annotations

import io
import logging
import re
import threading
from typing import Optional

from . import metrics as _mx

log = logging.getLogger(__name__)

_lock = threading.Lock()
_programs: dict[str, dict] = {}    # name -> cost/memory entry
_buffers: dict[str, int] = {}      # kind -> resident bytes
_enabled = True

# cost_analysis keys -> ledger/gauge field names
_COST_KEYS = (("flops", "flops"), ("bytes accessed", "bytes"))
# CompiledMemoryStats attributes -> ledger/gauge field names
_MEM_ATTRS = (("argument_size_in_bytes", "hbm_args"),
              ("output_size_in_bytes", "hbm_out"),
              ("temp_size_in_bytes", "hbm_temp"),
              ("generated_code_size_in_bytes", "hbm_code"))

# the attribute a Pallas TPU call carries in the lowered module
_KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"')

# program name -> recorder span name whose wall time measures it. Multiple
# training programs share the "train" span (per-round vs blocked vs chunked
# mode — only one is active in a given run; chunk+finalize split one span's
# wall, so their per-program MFU is a lower bound, stated in the README).
SPAN_OF_PROGRAM = {"round_fn": "train", "block_fn": "train",
                   "chunk_fn": "train", "finalize_fn": "train",
                   "eval_fn": "eval"}


def set_enabled(on: bool) -> None:
    """Master switch (bench.py's w1_attribution_overhead_pct measures the
    plane against this off-state)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Drop captured programs and buffer entries (tests)."""
    with _lock:
        _programs.clear()
        _buffers.clear()


def programs() -> dict:
    """{program name: {flops, bytes, hbm_*, calls, kernels}} — a copy;
    `kernels` lists the Mosaic (Pallas TPU) kernel names in the program."""
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


def buffers() -> dict:
    """{kind: resident bytes} of every registered device pytree."""
    with _lock:
        return dict(_buffers)


def _abstract_signature(args: tuple, kwargs: dict):
    """The call's shapes/dtypes as ShapeDtypeStructs — valid `lower()`
    input even after the concrete (possibly donated) buffers are gone:
    aval metadata survives buffer deletion. A sharding rides only where
    the array was COMMITTED to it, which is exactly what the call itself
    honoured: jit moves an uncommitted array (a fresh `jnp.asarray`) to
    wherever the committed ones live, while pinning it to its default
    device here would make a mesh program's lower() refuse the mixed
    device set — and would make even a single-device lowering a different
    program to the compilation cache than the one the call just stored."""
    import jax

    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sharding = (getattr(x, "sharding", None)
                        if getattr(x, "committed", False) else None)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x

    return jax.tree_util.tree_map(spec, (args, kwargs))


def _mosaic_kernels(lowered) -> list:
    """Names of the Pallas kernels the program hands to Mosaic, read off the
    lowered module itself: an interpret-mode kernel lowers to plain HLO and
    leaves no tpu_custom_call, so chip_smoke.py can assert the compiled path
    from the program, not from a flag. Printing a module costs ~6 ms per
    100 KB; the bytecode probe keeps the usual answer — none, for every
    program of a CPU run — at ~1 ms."""
    buf = io.BytesIO()
    lowered.compiler_ir().operation.write_bytecode(file=buf)
    if b"tpu_custom_call" not in buf.getvalue():
        return []
    return sorted(set(_KERNEL_NAME.findall(lowered.as_text())))


def _cost_fields(cost) -> dict:
    """{flops, bytes} out of whatever shape cost_analysis() answered in
    (a dict, a one-element list of dicts, or None)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    cost = cost or {}
    return {field: float(cost[key]) for key, field in _COST_KEYS
            if cost.get(key) is not None}


def note_call(name: str) -> None:
    """Steady-state per-call accounting: total executed FLOPs for a
    program = captured per-call FLOPs x this counter."""
    if _enabled:
        _mx.inc(f"xla.program.calls.{name}")


def _aval_bytes(tree) -> int:
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            n = 1
            for d in shape:
                n *= int(d)
            total += n * dtype.itemsize
    return total


def capture(name: str, jitted, args: tuple, kwargs: dict) -> None:
    """AOT-resolve cost analysis for `jitted` at this call's signature
    and publish the `xla.program.*` gauges. Called by `_TrackedJit` only
    when the compile cache grew. COMPILE-FREE by default: the lowering
    answers cost_analysis and the avals give argument/output bytes —
    `lower().compile()` would NOT reuse the call path's executable and a
    duplicate XLA compile per program is exactly the overhead the bench
    row bounds. `FEDML_TPU_XLA_DEEP=1` opts into the real compile for
    `memory_analysis()` temps. Never raises."""
    import os

    if not _enabled:
        return
    try:
        import jax

        spec_args, spec_kwargs = _abstract_signature(args, kwargs)
        lowered = jitted.lower(*spec_args, **spec_kwargs)
        ent = _cost_fields(lowered.cost_analysis())
        compiled = None
        if "flops" not in ent:
            # XLA:TPU answers cost_analysis for a COMPILED program only
            # (the lowering's is None there). Every entry point keeps the
            # persistent compilation cache on, so this compile is a load
            # of the executable the call has just built and stored.
            compiled = lowered.compile()
            ent = _cost_fields(compiled.cost_analysis())
        ent["hbm_args"] = _aval_bytes((spec_args, spec_kwargs))
        ent["hbm_out"] = _aval_bytes(
            jax.eval_shape(jitted, *spec_args, **spec_kwargs))
        ent["hbm_peak"] = ent["hbm_args"] + ent["hbm_out"]
        kernels = _mosaic_kernels(lowered)
        if os.environ.get("FEDML_TPU_XLA_DEEP") == "1":
            mem = (compiled or lowered.compile()).memory_analysis()
            for attr, field in _MEM_ATTRS:
                v = getattr(mem, attr, None)
                if v is not None:
                    ent[field] = int(v)
            ent["hbm_peak"] = (ent["hbm_args"] + ent["hbm_out"]
                               + ent.get("hbm_temp", 0))
    except Exception as e:  # noqa: BLE001 — ledger must never break a step
        log.warning("xla ledger: capture failed for %s: %s: %s",
                    name, type(e).__name__, e)
        return
    with _lock:
        _programs.setdefault(name, {}).update(ent, kernels=kernels)
    for field, v in ent.items():
        _mx.set_gauge(f"xla.program.{field}.{name}", v)


def register_buffers(kind: str, tree) -> int:
    """Record a resident device pytree in the memory ledger: sums leaf
    nbytes into the `xla.ledger.<kind>_bytes` gauge and refreshes the
    `xla.ledger.device_bytes` total. Re-registration replaces the entry
    (a hot-swap or re-built carry reports its new size)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    with _lock:
        _buffers[kind] = total
        device_total = sum(_buffers.values())
    _mx.set_gauge(f"xla.ledger.{kind}_bytes", total)
    _mx.set_gauge("xla.ledger.device_bytes", device_total)
    return total


def measured_mfu(summary: Optional[dict] = None,
                 peak_flops_per_s: Optional[float] = None) -> dict:
    """Per-program utilization from measured span wall time over
    cost-analysis FLOPs: {program: {total_flops, wall_s, flops_per_s,
    mfu}}. `summary` defaults to the process recorder's span summary;
    `peak_flops_per_s` to the device's spec peak (None on CPU — mfu is
    then None, flops_per_s still reported). Publishes
    `xla.program.flops_per_s.*` (+ `xla.program.mfu.*` when a peak is
    known) gauges as a side effect."""
    if summary is None:
        from .events import recorder

        summary = recorder.summary()
    if peak_flops_per_s is None:
        try:
            from .flops import tpu_spec_peak_tflops

            peak_t = tpu_spec_peak_tflops()
            peak_flops_per_s = peak_t * 1e12 if peak_t is not None else None
        except Exception:  # noqa: BLE001 — no jax/devices in this process
            peak_flops_per_s = None
    out: dict = {}
    progs = programs()
    for prog, span in SPAN_OF_PROGRAM.items():
        ent = progs.get(prog)
        row = summary.get(span)
        if not ent or not ent.get("flops") or not row or not row["total_s"]:
            continue
        calls = int(_mx.registry.counter(
            f"xla.program.calls.{prog}").value())
        if calls <= 0:
            continue
        total_flops = ent["flops"] * calls
        wall = float(row["total_s"])
        fps = total_flops / wall
        mfu = (fps / peak_flops_per_s) if peak_flops_per_s else None
        out[prog] = {"total_flops": total_flops, "wall_s": wall,
                     "flops_per_s": fps, "mfu": mfu}
        _mx.set_gauge(f"xla.program.flops_per_s.{prog}", fps)
        if mfu is not None:
            _mx.set_gauge(f"xla.program.mfu.{prog}", mfu)
    return out
