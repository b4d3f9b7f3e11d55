"""`scope_roofline`: least time the chip could take for the work done under
a scope, over the device time of the operations under it. Where
`kernel_roofline` picks a kernel's events by name, this picks every
operation whose innermost scope is one of `leaf` (forward, backward and
recomputed alike), so it reads the same work whatever implements it: a
Pallas kernel, a ragged product, padded dense products. The work is the
metric's work function's, in total over the traced window, from the
harness's log."""
from __future__ import annotations

import re

from chipbench import manifest, reduce


def scope_roofline(spec: dict, trace: dict, ctx: dict):
    rx = [re.compile(p) for p in spec.get("programs", [])]
    leaves = set(spec["leaf"])
    took = reduce.union_ns(
        (start, start + dur)
        for (_n, start, dur), prog, path in reduce.scoped_ops(trace)
        if reduce.leaf(path) in leaves
        and (not rx or any(r.search(prog) for r in rx))) / 1e9
    if took <= 0:
        return None
    w = manifest.find("work", spec["work"])(ctx["cell"], ctx["log"])
    peaks = ctx["peaks"]
    least = max(w["flops"] / peaks["bf16_flops_per_s"],
                w["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
