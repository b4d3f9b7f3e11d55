"""What the plain references share: the precisions a reference can be
computed in (the stated one, and the lower ones a control uses)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _through(dtype, top: float):
    """x as `dtype` holds it, scaled per tensor so that its largest entry
    sits at `top`."""
    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / s).astype(dtype).astype(jnp.float32) * s
    return q


def rounder(precision: str):
    """operand -> operand as a unit of `precision` would see it. "f32" is
    the reference itself. "fp8", the control for a configuration that
    states bfloat16, rounds every matmul/conv operand on the way in (e4m3,
    scaled per tensor) and the gradient that comes back through it on the
    way out (e5m2), as a unit that computes forward and backward in fp8
    would; the arithmetic around the rounding stays float32."""
    if precision == "f32":
        return lambda x: x
    if precision == "fp8":
        fwd = _through(jnp.float8_e4m3fn, 240.0)
        bwd = _through(jnp.float8_e5m2, 28672.0)
    else:
        raise ValueError(f"unknown precision {precision!r}")

    @jax.custom_vjp
    def rnd(x):
        return fwd(x)

    rnd.defvjp(lambda x: (fwd(x), None), lambda _, g: (bwd(g),))
    return rnd


def softmax_ce(logits, y):
    """Mean cross-entropy of integer labels, float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
