"""Sparse expert feed-forward layer, held a share at a time.

The layer a mixture-of-experts decoder has in the dense feed-forward's
place: a router scores every token against ALL `n_experts` experts (float32;
`scoring` "sigmoid": the `top_k` largest of score + selection bias are
chosen; "softmax": a softmax over all experts, the `top_k` largest chosen,
no bias), their scores are normalised over the chosen and scaled, and the
token's output is the weighted sum of the chosen experts' gated units (`act`:
SwiGLU, or ReGLU, relu(gate) * up) plus, where `n_shared` > 0, a shared
expert every token passes through. The router reads the rows the layer is
handed, or (`route_from` "attn") the rows its block hands it beside them:
the attention norm's output, read before attention.

Expert parallelism divides the experts over chips. This module is told which
experts it HOLDS (`MoE.held = (first, count)`): it routes over all of them,
normalises over all the chosen whether held here or not, and computes the
part of the sum its own experts give. On one chip that partial sum (plus the
shared expert) is the layer's output; across chips an exchange would bring
the tokens in and the parts back, and nothing here stands in for it.

No token is dropped, whatever the imbalance: the (token, expert) pairs routed
here are sorted by expert into one row buffer whose SHAPE is the worst case
(every token choosing `top_k` held experts), of which only the first `n_here`
rows, the pairs that did land here, are ever moved or multiplied. A grouped
matrix product (Pallas `megablox.gmm`, whose grid is sized by the group sizes
at run time) multiplies each expert's rows by its weights, and the moves
around it (`_dispatch` tokens to rows, `_combine` rows back to tokens, each
with the other's form as its backward) are the Pallas kernels of
ops/routed_rows.py, whose walk is bounded by `n_here` at run time: rows past
it are neither read nor written. The product's row tile is the rows an
expert can expect (`row_tile`): megablox multiplies a whole tile for every
tile a live expert's rows touch, so a 512-row tile over an expert holding
three rows is MXU time spent on rows that do not exist.

Scopes (PERF.md section 3): `moe.route`, `moe.dispatch`, `moe.experts`,
`moe.combine`, `moe.shared`; the live-row kernels of the gated unit are
`moe_swiglu` / `moe_swiglu_bwd`, or `moe_reglu` / `moe_reglu_bwd`.
Counters, sown into the `counters` collection (`llm.federated_lora` reads
them into the round's metrics): `moe_pairs`, the
pairs computed here in this call, `moe_max_rows`, the rows of the fullest
held expert, `moe_rows_walked`, the buffer rows the dispatch walked
(`moe_pairs` rounded up to the kernel's row block: over tokens x top_k it
says how far the bound engages, 1.0 when every token chooses held experts),
and, where the caller says which rows are live (the decode programs),
`moe_experts_live`, the held experts with at least one row (whose weights
the grouped product has to read), and `moe_tile_rows`, the rows the grouped
product multiplies (`tile_rows`: `moe_pairs / moe_tile_rows` is the tiles'
fill).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from ..ops import routed_rows
from ..ops.flash_attention import _auto_block, _auto_interpret

COUNTERS = "counters"
# (rows, contraction, columns) tile caps of the grouped product; each is
# halved until it divides (rows) or capped at the dimension
GMM_TILES = (512, 1024, 1024)
# the least row tile: the v5e MXU's rows (a smaller tile saves no MXU time,
# and at a few rows an expert the weights' read sets the pace)
GMM_MIN_ROWS = 128


@dataclasses.dataclass(frozen=True)
class MoE:
    """One expert layer's shape. `n_experts` is the router's width (every
    expert of the layer, wherever it lives); `held` = (first, count) names
    the experts this module holds, None for all of them. `scoring` is the
    router's: "sigmoid" scores chosen by score + a selection bias, or
    "softmax" over all experts with no bias. `n_shared` = 0 builds no shared
    expert. `act` is the experts' gated unit, "swiglu" (silu(gate) * up) or
    "reglu" (relu(gate) * up); `route_from` says what the router reads,
    "ffn" the feed-forward norm's output (the rows the layer multiplies) or
    "attn" the block's attention norm's output, before attention."""
    n_experts: int
    top_k: int
    d_expert: int
    held: Optional[tuple] = None
    n_shared: int = 1
    scale: float = 1.0           # routed_scaling_factor
    norm_topk: bool = True
    scoring: str = "sigmoid"
    act: str = "swiglu"
    route_from: str = "ffn"

    def __post_init__(self):
        for field, allowed in (("scoring", ("sigmoid", "softmax")),
                               ("act", ("swiglu", "reglu")),
                               ("route_from", ("ffn", "attn"))):
            if getattr(self, field) not in allowed:
                raise ValueError(
                    f"{field} must be " + " or ".join(map(repr, allowed))
                    + f", got {getattr(self, field)!r}")

    @property
    def first(self) -> int:
        return self.held[0] if self.held else 0

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts


def fold_counters(sown) -> dict:
    """{name: one number} of a `counters` collection: a name holding `max`
    folds by maximum over the layers that sowed it, every other by sum."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(sown)[0]:
        name = next(str(p.key) for p in reversed(path) if hasattr(p, "key"))
        leaf = jnp.asarray(leaf, jnp.float32)
        if name not in out:
            out[name] = leaf
        elif "max" in name:
            out[name] = jnp.maximum(out[name], leaf)
        else:
            out[name] = out[name] + leaf
    return out


@jax.custom_vjp
def _dispatch(x, order, inv, here, n_here):
    """Tokens to buffer rows: row r < n_here is the token of pair
    `order[r]`. x [N, d]; order [P] the pairs sorted by held expert, inv
    [N, k] its inverse (pair -> row), here [N, k] the pairs routed here."""
    return routed_rows.rows_out(x, order // inv.shape[1], n_here)


def _dispatch_fwd(x, order, inv, here, n_here):
    return _dispatch(x, order, inv, here, n_here), (inv, here, n_here)


@jax.jit
def _dispatch_bwd(res, g):
    inv, here, n_here = res
    # a token gets the sum of the cotangent rows it was copied to
    return (routed_rows.rows_back(g, inv, here, None, n_here),
            None, None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, order, inv, here, n_here):
    """Buffer rows back to tokens: token n gets `sum_j w[n, j] ys[inv[n,
    j]]` over its pairs routed here, accumulated in float32."""
    return routed_rows.rows_back(ys, inv, here, w, n_here)


def _combine_fwd(ys, w, order, inv, here, n_here):
    return (_combine(ys, w, order, inv, here, n_here),
            (ys, w, order, inv, here, n_here))


@jax.jit
def _combine_bwd(res, dy):
    ys, w, order, inv, here, n_here = res
    # row r gets its pair's weight times its token's cotangent; a weight
    # gets its row's product with it (the weights depend on h through the
    # router's scores)
    dys = routed_rows.rows_out(dy, order // w.shape[1], n_here,
                               w.reshape(-1)[order])
    dw = routed_rows.rows_dots(ys, inv, here, dy, n_here)
    return dys, dw.astype(w.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _silu_mul(gate, up):
    g, u = gate.astype(jnp.float32), up.astype(jnp.float32)
    return (g * jax.nn.sigmoid(g) * u,)


def _silu_mul_grads(gate, up, dact):
    g, u, da = (a.astype(jnp.float32) for a in (gate, up, dact))
    sg = jax.nn.sigmoid(g)
    return da * u * sg * (1.0 + g * (1.0 - sg)), da * g * sg


@jax.custom_vjp
@jax.jit
def _swiglu(gate, up, n_here):
    """silu(gate) * up on the buffer's live rows (float32 inside, rounded
    once); rows past their last block hold whatever the buffer held."""
    return routed_rows.live_map(
        _silu_mul, n_here, [gate, up], [(gate.shape[1:], gate.dtype)],
        name="moe_swiglu")[0]


def _swiglu_fwd(gate, up, n_here):
    return _swiglu(gate, up, n_here), (gate, up, n_here)


@jax.jit
def _swiglu_bwd(res, dact):
    gate, up, n_here = res
    dgate, dup = routed_rows.live_map(
        _silu_mul_grads, n_here, [gate, up, dact],
        [(gate.shape[1:], gate.dtype), (up.shape[1:], up.dtype)],
        name="moe_swiglu_bwd")
    return dgate, dup, None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _relu_mul(gate, up):
    g, u = gate.astype(jnp.float32), up.astype(jnp.float32)
    return (jnp.maximum(g, 0.0) * u,)


def _relu_mul_grads(gate, up, dact):
    g, u, da = (a.astype(jnp.float32) for a in (gate, up, dact))
    return jnp.where(g > 0.0, da * u, 0.0), da * jnp.maximum(g, 0.0)


@jax.custom_vjp
@jax.jit
def _reglu(gate, up, n_here):
    """relu(gate) * up on the buffer's live rows, as `_swiglu`."""
    return routed_rows.live_map(
        _relu_mul, n_here, [gate, up], [(gate.shape[1:], gate.dtype)],
        name="moe_reglu")[0]


def _reglu_fwd(gate, up, n_here):
    return _reglu(gate, up, n_here), (gate, up, n_here)


@jax.jit
def _reglu_bwd(res, dact):
    gate, up, n_here = res
    dgate, dup = routed_rows.live_map(
        _relu_mul_grads, n_here, [gate, up, dact],
        [(gate.shape[1:], gate.dtype), (up.shape[1:], up.dtype)],
        name="moe_reglu_bwd")
    return dgate, dup, None


_reglu.defvjp(_reglu_fwd, _reglu_bwd)
_GATED = {"swiglu": _swiglu, "reglu": _reglu}


@jax.custom_vjp
def _twice(xs, n_here):
    """(xs, xs), for the two products that read the buffer: the sum of their
    cotangents, which jax would add over the whole buffer, is taken over
    the live rows."""
    return xs, xs


def _twice_fwd(xs, n_here):
    return (xs, xs), n_here


@jax.jit
def _twice_bwd(n_here, gs):
    a, b = gs
    return routed_rows.live_map(
        lambda a, b: (a.astype(jnp.float32) + b.astype(jnp.float32),),
        n_here, [a, b], [(a.shape[1:], a.dtype)], name="moe_rows_add")[0], None


_twice.defvjp(_twice_fwd, _twice_bwd)


def row_tile(p: int, n_experts: int) -> int:
    """The grouped product's row tile for a buffer of `p` rows routed over
    a router `n_experts` wide: the rows an expert can expect under even
    routing, rounded up to a power of two and clamped to [GMM_MIN_ROWS,
    GMM_TILES[0]], then halved until it divides `p`. The tile decides how
    many rows the MXU multiplies and throws away, never a sum."""
    even = -(-p // n_experts)
    tm = min(max(1 << (even - 1).bit_length(), GMM_MIN_ROWS), GMM_TILES[0])
    return _auto_block(p, tm)


def tile_rows(sizes, tm: int):
    """Rows megablox multiplies at row tile `tm` over groups of `sizes`
    rows, laid end to end: `tm` x the row tiles each nonempty group's range
    touches (empty groups are not visited)."""
    ends = jnp.cumsum(sizes)
    touched = (ends + tm - 1) // tm - (ends - sizes) // tm
    return tm * jnp.sum(jnp.where(sizes > 0, touched, 0))


def grouped_matmul(rows, weights, sizes, n_experts: int):
    """rows [P, K] sorted by group, weights [G, K, N], sizes [G] (their
    sum at most P), routed by a router `n_experts` wide: rows of group g
    times weights[g]. Rows past the sum are not computed and hold whatever
    the buffer held."""
    p, k = rows.shape
    tiles = (row_tile(p, n_experts), min(k, GMM_TILES[1]),
             min(weights.shape[-1], GMM_TILES[2]))
    return megablox.gmm(rows, weights, sizes, rows.dtype, tiles,
                        interpret=_auto_interpret())


def route(h, kernel, bias, spec: MoE):
    """(chosen experts [N, k] int32, their weights [N, k] float32): scores
    sigmoid(h Wr) in float32 over all experts, the k largest of score +
    bias chosen (the bias chooses only), weights the chosen scores
    normalised over all k and scaled. With `scoring` "softmax" the scores
    are softmax(h Wr) over all experts and choose themselves (`bias` is
    None)."""
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=hi)
    if spec.scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(s, spec.top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), spec.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if spec.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, spec.scale * w


class Kernel(nn.Module):
    """One weight under the leaf name `kernel` (the role LoRA, the
    quantiser and the benchmark's initialiser read off a path): [din, dout],
    or [experts, din, dout] for the held experts' stack."""
    shape: tuple

    @nn.compact
    def __call__(self):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=tuple(range(len(self.shape) - 2)))
        return self.param("kernel", init, self.shape)


class ExpertLayer(nn.Module):
    """[B, T, d] -> [B, T, d]: the held experts' part of the routed sum
    plus the shared expert. `live` [B, T] bool (the decode programs': an
    idle slot's rows, a chunk's padding) routes the other rows nowhere:
    they are neither moved nor multiplied, touch no expert's weights and
    come back as the shared expert's part alone. `route_x` [B, T, d] is
    what the router reads where the spec's `route_from` is "attn" (the
    block's attention norm's output); None: the rows `h` themselves."""
    spec: MoE

    @nn.compact
    def __call__(self, h, live=None, route_x=None):
        sp = self.spec
        d = h.shape[-1]
        rows = h.reshape(-1, d)
        n, k, f = rows.shape[0], sp.top_k, sp.d_expert
        if (route_x is None) != (sp.route_from == "ffn"):
            raise ValueError(
                f"an expert layer routed from {sp.route_from!r} takes "
                "`route_x` exactly when it reads the attention norm's output")

        def expert_kernel(name, din, dout):
            return Kernel((sp.n_held, din, dout), name=f"experts_{name}")()

        with jax.named_scope("moe.route"):
            idx, w = route(
                rows if route_x is None else route_x.reshape(-1, d),
                Kernel((d, sp.n_experts), name="router")(),
                self.param("e_score_correction_bias", nn.initializers.zeros,
                           (sp.n_experts,))
                if sp.scoring == "sigmoid" else None, sp)

        with jax.named_scope("moe.dispatch"):
            # pairs (token, slot) routed to a held expert, sorted by expert;
            # the others sort to the end and are never multiplied
            local = idx - sp.first
            here = (local >= 0) & (local < sp.n_held)              # [N, k]
            if live is not None:
                here = here & live.reshape(-1, 1)
            key = jnp.where(here, local, sp.n_held).reshape(-1)    # [P]
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inv = jnp.argsort(order).astype(jnp.int32)     # pair -> its row
            sizes = jnp.sum(key[:, None] == jnp.arange(sp.n_held)[None, :],
                            axis=0, dtype=jnp.int32)
            n_here = jnp.sum(sizes)
            inv = inv.reshape(n, k)
            xs = _dispatch(rows, order, inv, here, n_here)
        self.sow(COUNTERS, "moe_pairs", n_here)
        self.sow(COUNTERS, "moe_max_rows", jnp.max(sizes))
        self.sow(COUNTERS, "moe_rows_walked",
                 routed_rows.rows_walked(n_here, n * k))
        if live is not None:
            self.sow(COUNTERS, "moe_experts_live",
                     jnp.sum(sizes > 0, dtype=jnp.int32))
            self.sow(COUNTERS, "moe_tile_rows",
                     tile_rows(sizes, row_tile(n * k, sp.n_experts)))

        with jax.named_scope("moe.experts"):
            xs_gate, xs_up = _twice(xs, n_here)
            gate = grouped_matmul(xs_gate, expert_kernel("w_gate", d, f),
                                  sizes, sp.n_experts)
            up = grouped_matmul(xs_up, expert_kernel("w_up", d, f), sizes,
                                sp.n_experts)
            ys = grouped_matmul(_GATED[sp.act](gate, up, n_here),
                                expert_kernel("w_down", f, d), sizes,
                                sp.n_experts)

        with jax.named_scope("moe.combine"):
            y = _combine(ys, w, order, inv, here, n_here)

        if not sp.n_shared:
            return y.reshape(h.shape)
        with jax.named_scope("moe.shared"):
            wide = f * sp.n_shared
            gate = nn.Dense(wide, use_bias=False, name="shared_w_gate")(rows)
            up = nn.Dense(wide, use_bias=False, name="shared_w_up")(rows)
            y = y + nn.Dense(d, use_bias=False, name="shared_w_down")(
                nn.silu(gate) * up)
        return y.reshape(h.shape)
