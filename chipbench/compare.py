"""The comparison that decides `correct` in a training cell.

The program's first three steps against the plain reference's: each step's
loss, the first gradient as the optimizer gets it (the state's change after
one step) and the parameters' change after the three, both by the WORST
LEAF. A leaf's gap is the gap between the two norms (not the norm of the
difference), against the reference's norm of that leaf or of the median
leaf, whichever is larger, since some gradients are all but zero. Beside each
worst leaf's gap stands the MEDIAN leaf's, which is steady from seed to seed
where the worst is the noise of one small leaf (PERF.md section 6 says which
of them each cell's limits hold).
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from chipbench.inputs import path_str

TINY_GRADIENT = 1e-3     # of the median leaf's: moved by round-off alone


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(_norms(tree)))
    return {path_str(p): float(v) for p, v in flat}


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


def leaf_gaps(got: dict, ref: dict, skip=()) -> dict[str, float]:
    med = statistics.median(ref.values())
    return {k: abs(got[k] - r) / max(r, med, 1e-30)
            for k, r in ref.items() if k not in skip}


def worst_and_median(gaps: dict) -> tuple[float, str, float]:
    """(the worst leaf's gap, that leaf, the median leaf's gap); a NaN gap
    is the worst there is and makes the median NaN too."""
    worst, where = 0.0, ""
    for k, gap in gaps.items():
        if worst == worst and not gap <= worst:
            worst, where = gap, k
    vals = list(gaps.values())
    med = (float("nan") if any(v != v for v in vals)
           else statistics.median(vals))
    return worst, where, med


def training_numbers(got: dict, ref: dict) -> dict[str, float]:
    """`got` and `ref`: {"loss": [..], "grad1": norms, "change": norms}.
    Returns the numbers compared, by the names the limits use."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], ref["loss"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    out["grad1_gap"], out["_grad1_leaf"], out["grad1_median_gap"] = \
        worst_and_median(leaf_gaps(got["grad1"], ref["grad1"]))
    med = statistics.median(ref["grad1"].values())
    still = [k for k, v in ref["grad1"].items() if v < TINY_GRADIENT * med]
    out["change_gap"], out["_change_leaf"], out["change_median_gap"] = \
        worst_and_median(leaf_gaps(got["change"], ref["change"], skip=still))
    out["_leaves_left_out"] = still
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct only if every one is within."""
    rows = {k: {"value": numbers[k], "limit": lim}
            for k, lim in limits.items()}
    ok = all(r["value"] <= r["limit"] for r in rows.values())   # NaN fails
    return ok, rows
