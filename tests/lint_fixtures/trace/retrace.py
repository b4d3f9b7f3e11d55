"""graftlint fixture: retrace-hazard (positive + negative + suppressed).
Never imported — parsed by the linter only."""
import jax
from jax import shard_map


def bad_loop(fns, xs):
    outs = []
    for f in fns:
        outs.append(jax.jit(f)(xs))          # FINDING: jit built per iter
    return outs


def bad_comprehension(fns):
    return [jax.jit(f) for f in fns]         # FINDING: jit per element


def bad_while(f, xs, mesh, spec):
    while xs:
        step = shard_map(f, mesh=mesh,       # FINDING: shard_map in loop
                         in_specs=spec, out_specs=spec)
        xs = step(xs)
    return xs


def ok_hoisted(f, xs):
    step = jax.jit(f)
    return [step(x) for x in xs]             # call in loop is fine


def silenced(fns, xs):
    outs = []
    for f in fns:
        outs.append(jax.jit(f)(xs))  # graftlint: disable=retrace-hazard (fixture: deliberate)
    return outs
