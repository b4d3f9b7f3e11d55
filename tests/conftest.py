"""Test conftest: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's "multi-node without a cluster" CI strategy
(reference: python/tests/cross-silo/run_cross_silo.sh:1-28 fakes multi-node with
multi-process on one box); here we fake a TPU pod with
--xla_force_host_platform_device_count on CPU. Tests never touch a chip:
JAX_PLATFORMS=cpu is pinned here, before `import jax`, whatever the caller's
environment says.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Every entry point turns the persistent compilation cache on
# (utils.enable_compilation_cache). The suite keeps it OFF: a cache left
# warm by an earlier run would change which tests compile at all, and the
# tests that count compiles must see the same thing on every run.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _isolate_recorder():
    """Tests share the PROCESS-GLOBAL event recorder (utils/events.py);
    snapshot its state (spans, metric rows, sinks, the exact-count summary
    aggregate) before each test and restore it after, so one test's
    telemetry can't satisfy — or pollute — another test's assertions.
    Background daemons a test failed to stop may append during restore;
    that's the same leak the fixture existed to contain, just one row of
    it."""
    from fedml_tpu.utils.events import recorder

    spans, metrics = list(recorder.spans), list(recorder.metrics)
    sinks = list(recorder.sinks)
    agg = {k: dict(v) for k, v in recorder.summary().items()}
    dropped = dict(recorder.dropped)
    dropped_rows = recorder.dropped_rows
    yield
    recorder.spans.clear()
    recorder.spans.extend(spans)
    recorder.metrics.clear()
    recorder.metrics.extend(metrics)
    recorder.sinks[:] = sinks
    with recorder._agg_lock:
        recorder._agg.clear()
        recorder._agg.update(agg)
        recorder.dropped.clear()
        recorder.dropped.update(dropped)
        recorder.dropped_rows = dropped_rows


@pytest.fixture(autouse=True)
def _isolate_xla_ledger():
    """The XLA cost/memory ledger (utils/xla_ledger.py, ISSUE 17) keeps
    process-global program/buffer dicts; snapshot and restore them so one
    test's captures can't satisfy another's assertions."""
    from fedml_tpu.utils import xla_ledger

    progs = xla_ledger.programs()
    bufs = xla_ledger.buffers()
    enabled = xla_ledger.enabled()
    yield
    with xla_ledger._lock:
        xla_ledger._programs.clear()
        xla_ledger._programs.update(progs)
        xla_ledger._buffers.clear()
        xla_ledger._buffers.update(bufs)
    xla_ledger.set_enabled(enabled)


@pytest.fixture(autouse=True)
def _isolate_flight_recorder():
    """The crash flight recorder (utils/postmortem.py, ISSUE 18) is a
    process-global ring + arm state; a test that arms it must not leave
    the spill thread pointed at its (deleted) tmp dir for the next test.
    Disarm and clear the rings afterwards; re-enable in case a test
    toggled it off."""
    from fedml_tpu.utils import postmortem as pm

    yield
    if pm.flight.armed_dir is not None:
        pm.flight.disarm()
    pm.flight._spans.clear()
    pm.flight._frames.clear()
    pm.flight.set_enabled(True)
    pm.flight.process = "main"


@pytest.fixture(autouse=True)
def _isolate_metrics_registry():
    """The recorder fixture above left the process-global MetricsRegistry
    (utils/metrics.py) shared across tests, so counter assertions (e.g.
    test_comm_bench's byte floors) could bleed across test order. Swap in a
    fresh registry per test — every writer resolves `metrics.registry` at
    call time, so in-flight instruments from daemons a previous test leaked
    keep writing into the OLD registry harmlessly — and restore the
    original afterwards."""
    from fedml_tpu.utils import metrics as mx

    prev = mx.registry
    mx.registry = mx.MetricsRegistry()
    yield
    mx.registry = prev
