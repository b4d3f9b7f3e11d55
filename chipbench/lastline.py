"""The one check every run passes its last line through before printing it.

`problems(obj, manifest, workload, traced)` returns the reasons the object
is not a result the driver would take; an empty list means it is one. PR 22
was refused for a last line that failed the driver's reading: this function
reads it the same way, from BENCHMARK.json alone.
"""
from __future__ import annotations

import math

from chipbench.manifest import metrics_for

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def is_share(metric: dict) -> bool:
    """A share of a roofline or of the chip's peak: cannot pass 100%."""
    name = metric["name"]
    return metric["unit"] == "%" and (
        "roofline" in name or "mfu" in name.replace(".", "_").split("_"))


def problems(obj, manifest: dict, workload: str, traced: bool,
             result: bool = True) -> list[str]:
    """Why `obj` is not a valid last line for this cell in this mode.
    `result=False` checks the form only (a CPU rehearsal's line)."""
    out: list[str] = []
    if not isinstance(obj, dict):
        return ["the last line is not a JSON object"]
    for k in REQUIRED:
        if k not in obj:
            out.append(f"key {k!r} is missing")
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not (isinstance(obj[k], int) and not isinstance(obj[k], bool)
                and obj[k] >= 0):
            out.append(f"{k!r} is not a whole number >= 0")
    if not out and obj["failed"] > obj["attempted"]:
        out.append("'failed' exceeds 'attempted'")

    want = {m["name"]: m for m in metrics_for(manifest, workload, traced)}
    got = obj["metrics"]
    if not isinstance(got, dict):
        out.append("'metrics' is not an object")
        got = {}
    for name, m in want.items():
        if name not in got:
            out.append(f"metric {name!r} is missing ("
                       f"{'per-layer, traced' if traced else 'end-to-end'})")
            continue
        row = got[name]
        if not (isinstance(row, dict) and "value" in row and "unit" in row):
            out.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if row["unit"] != m["unit"]:
            out.append(f"metric {name!r} has unit {row['unit']!r}, the "
                       f"manifest says {m['unit']!r}")
        if not _number(row["value"]):
            out.append(f"metric {name!r} is not a finite number: "
                       f"{row['value']!r}")
        elif is_share(m) and not 0 < row["value"] <= 100:
            out.append(f"share {name!r} = {row['value']} is outside (0, 100]")
        elif not traced and row["value"] <= 0:
            out.append(f"end-to-end metric {name!r} = {row['value']} is not "
                       "above 0")
    for name in got:
        if name not in want:
            out.append(f"metric {name!r} is not one the manifest gives "
                       f"{workload} in this mode")

    dev = obj["device"]
    if not isinstance(dev, dict):
        return out + ["'device' is not an object"]
    for k in DEVICE_KEYS:
        if k not in dev:
            out.append(f"device.{k} is missing")
    if result and dev.get("platform") != "tpu":
        out.append(f"device.platform is {dev.get('platform')!r}, not 'tpu': "
                   "not a result")
    cell = {w["name"]: w for w in manifest["workloads"]}[workload]
    if result and dev.get("count") != cell["chips"]:
        out.append(f"device.count {dev.get('count')!r} is not the cell's "
                   f"{cell['chips']} chips")
    mem = dev.get("memory_peak_bytes")
    if "memory_peak_bytes" in dev and not (_number(mem) and mem > 0):
        out.append(f"device.memory_peak_bytes is {mem!r}")
    if traced:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not (_number(w) and w > 0):
            out.append(f"device.window_s is {w!r}")
        elif not (_number(b) and 0 < b <= w):
            out.append(f"device.busy_s {b!r} is not in (0, window_s={w}]")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                ok = (isinstance(rows, list) and len(rows) <= 10 and all(
                    isinstance(r, (list, tuple)) and len(r) == 2
                    and isinstance(r[0], str) and _number(r[1])
                    for r in rows))
                if not ok:
                    out.append(f"breakdown.{key} is not at most 10 "
                               "[name, seconds] pairs")
    return out
