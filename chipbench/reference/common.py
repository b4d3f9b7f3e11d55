"""What the plain references share: the precisions a reference can be
computed in (the stated one, and the lower ones a control uses), and, for a
language model, what follows from its `forward(params, tokens, model,
precision, adapters)` (logits [T, V] of ONE sequence) alone: the rounds of
federated LoRA the `fedlora` kind follows and the logits the `serve` kind
compares. A configuration's reference brings its forward."""
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


# ------------------------------------------- over a language model's forward
def lora_round(forward, base, adapters, x, y, lr, model, precision="f32",
               half_batch=False):
    """One round of federated LoRA where every silo takes ONE local SGD
    step over all its sequences: x, y [silos, seqs, T]. Every silo starts
    from the same adapters, so the mean of the silos' changes is -lr times
    the mean of their gradients. Returns (new adapters, mean loss).
    `half_batch` plants the fault "half of the batch left out"."""
    if half_batch:
        x, y = x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]
    rows_x = x.reshape(-1, x.shape[-1])
    rows_y = y.reshape(-1, y.shape[-1])
    n = rows_x.shape[0]

    def seq_loss(ad, xs, ys):
        return softmax_ce(forward(base, xs, model, precision, ad), ys)

    def one(acc, xy):
        loss, g = jax.value_and_grad(seq_loss)(adapters, *xy)
        return jax.tree.map(lambda a, b: a + b / n, acc, g), loss

    grad, losses = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, adapters),
                                (rows_x, rows_y))
    return (jax.tree.map(lambda a, g: a - lr * g, adapters, grad),
            jnp.mean(losses))


def run_lora(forward, base, adapters0, x, y, rounds: int, lr, model,
             precision="f32", half_batch=False):
    """Follow `rounds` rounds (the data is the same every round, as the
    program is given it). Returns the losses and the adapters after round 1
    and after the last."""
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                    tree)
    base, adapters0 = f32(base), f32(adapters0)
    step = jax.jit(lambda b, a, xs, ys: lora_round(
        forward, b, a, xs, ys, lr, model, precision, half_batch))
    ad, losses, first = adapters0, [], None
    for _ in range(rounds):
        ad, loss = step(base, ad, x, y)
        losses.append(float(loss))
        first = ad if first is None else first
    return {"loss": losses, "params": [first, ad], "params0": adapters0}


def sequence_logits(forward, params, model: dict, precision: str = "f32"):
    """A jitted tokens [T] -> logits [T, V] over float32 weights, for the
    served-token comparison (one prompt with its served tokens a call)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    fn = jax.jit(lambda p, toks: forward(p, toks, model, precision))
    return lambda tokens: fn(params, tokens)
