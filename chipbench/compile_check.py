#!/usr/bin/env python3
"""Compile a cell's timed programs for a described v5e at the real size,
in the sandbox, and print `memory_analysis()` against the chip's 16 GB.

    python3 chipbench/compile_check.py --workload resnet18gn_fedavg_c100

Nothing runs: the TPU compiler is given a `v5e:2x2` topology that is
described and not attached, so what it refuses (a kernel, a program that
does not fit) costs no chip time. It counts ONE program at a time, not what
else the process keeps on the device, and a compile that passes is not a
chip run. It covers the kinds that `build()` their program apart from
running it; the `serve` kind's programs are private to a running engine
(`DecodeEngine._step_jit`, `_admit_jit`) and are not covered here: the chip
run's own `memory_peak_bytes` is what is reported for that cell.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    driver = manifest.find("drivers", cell.driver)(cell, 1, False)
    if not hasattr(driver, "build"):
        print(f"compile_check: the {cell.driver!r} kind builds no program "
              "apart from running it (see this file's docstring)")
        return 0
    # the kernels choose interpret mode from the backend they find (the
    # CPU, here): steer them to the Mosaic path for this compile only
    from fedml_tpu.ops import flash_attention

    flash_attention._auto_interpret = lambda: False
    driver.build()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    hbm = manifest.load_json(manifest.HERE / "peaks.json")[
        "TPU v5 lite"]["hbm_bytes"]
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    for name, fn, fn_args in driver.programs():
        t0 = time.perf_counter()
        compiled = fn.lower(*jax.tree.map(spec, fn_args)).compile()
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"compile_check {cell.name} {name}: compiled for "
              f"{topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.1f} s; arguments "
              f"{ma.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{ma.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.2f} GB: {total / 1e9:.2f} GB "
              f"= {100 * total / hbm:.1f}% of the chip; "
              f"{kernels} Mosaic calls", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
