"""Plain reference of the `resnet18gn_cifar10` configuration under FedAvg.

ResNet-18 (He et al. 2016, basic blocks, widths 64-128-256-512) with
GroupNorm (Wu & He 2018, 32 groups, eps 1e-6) in place of BatchNorm and the
CIFAR stem (3x3, stride 1, no pooling), as FedML's `resnet18_gn` benchmark
model; FedAvg (McMahan et al. 2017): every client runs `epochs` of SGD over
its shard in batches, the server takes the sample-weighted mean of the
clients' changes. float32, highest matmul precision, one client at a time.

The parameter tree uses flax's automatic names (Conv_0, GroupNorm_0,
ResNetBlock_i/{Conv_j, GroupNorm_j}, Dense_0), which is how the harness
hands the weights over. Imports nothing from fedml_tpu.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, rounder, softmax_ce

GN_EPS = 1e-6


def _conv(x, w, stride, rnd):
    return jax.lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _group_norm(x, p, groups):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mean) * jax.lax.rsqrt(var + GN_EPS)).reshape(x.shape)
    return xn * p["scale"] + p["bias"]


def forward(params, x, model: dict, precision: str = "f32"):
    """Logits [B, classes] of images x [B, H, W, C]."""
    rnd = rounder(precision)
    groups = model["norm_groups"]
    h = jax.nn.relu(_group_norm(
        _conv(x, params["Conv_0"]["kernel"], 1, rnd),
        params["GroupNorm_0"], groups))
    k = 0
    for i, n_blocks in enumerate(model["stage_sizes"]):
        for j in range(n_blocks):
            p = params[f"ResNetBlock_{k}"]
            k += 1
            stride = 2 if i > 0 and j == 0 else 1
            y = jax.nn.relu(_group_norm(
                _conv(h, p["Conv_0"]["kernel"], stride, rnd),
                p["GroupNorm_0"], groups))
            y = _group_norm(_conv(y, p["Conv_1"]["kernel"], 1, rnd),
                            p["GroupNorm_1"], groups)
            if "Conv_2" in p:
                h = _group_norm(_conv(h, p["Conv_2"]["kernel"], stride, rnd),
                                p["GroupNorm_2"], groups)
            h = jax.nn.relu(y + h)
    h = jnp.mean(h, axis=(1, 2))
    d = params["Dense_0"]
    return jnp.matmul(rnd(h), rnd(d["kernel"]), precision=HI) + d["bias"]


def fedavg_round(params, x, y, batch_idx, weights, lr, model,
                 precision="f32", half_batch=False):
    """One FedAvg round. x [m, S, H, W, C], y [m, S], batch_idx [m, steps, B]
    (which rows each local step takes), weights [m] (sample counts).
    Returns (new params, mean training loss over every local step).
    `half_batch` plants the fault "half of the batch left out, the mean
    taken over the rest" (for the control runs, never for a reference)."""

    def loss_fn(p, xb, yb):
        if half_batch:
            xb, yb = xb[: xb.shape[0] // 2], yb[: yb.shape[0] // 2]
        return softmax_ce(forward(p, xb, model, precision), yb)

    w = weights / jnp.sum(weights)

    def client(acc, inp):
        xs, ys, idx, wi = inp

        def step(p, rows):
            loss, g = jax.value_and_grad(loss_fn)(p, xs[rows], ys[rows])
            return jax.tree.map(lambda a, b: a - lr * b, p, g), loss

        p, losses = jax.lax.scan(step, params, idx)
        acc = jax.tree.map(lambda s, a, b: s + wi * (a - b), acc, p, params)
        return acc, wi * jnp.mean(losses)

    zero = jax.tree.map(jnp.zeros_like, params)
    mean_delta, losses = jax.lax.scan(client, zero, (x, y, batch_idx, w))
    new = jax.tree.map(jnp.add, params, mean_delta)
    return new, jnp.sum(losses)


def run(params0, x, y, batch_idx_rounds, weights, lr, model,
        precision="f32", half_batch=False):
    """Follow the first len(batch_idx_rounds) rounds from params0. Returns
    {"loss": [...], "params": [after round 1, after the last round]}."""
    params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    step = jax.jit(lambda p, xs, ys, idx: fedavg_round(
        p, xs, ys, idx, weights, lr, model, precision, half_batch))
    p, losses, first = params0, [], None
    for idx in batch_idx_rounds:
        p, loss = step(p, x, y, idx)
        losses.append(float(loss))
        first = p if first is None else first
    return {"loss": losses, "params": [first, p], "params0": params0}
