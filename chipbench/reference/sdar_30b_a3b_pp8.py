"""Plain reference of the `sdar_30b_a3b_pp8` configuration, for serving.

SDAR-30B-A3B-Chat's decoder layer (Qwen3-MoE's block, which SDAR continues to
train) as configs/sdar_30b_a3b_pp8.json states it, and the family's published
generation loop: float32, highest matmul precision, no kernel, no cache, no
batching, one sequence at a time. Imports nothing from fedml_tpu nor from
chipbench/models.

Pre-norm block, N = RMSNorm (eps rms_norm_eps): a = h + Wo Attn(...),
out = a + Experts(N(a)); a final norm before the head.

  attention: q = N(h) Wq [H x dh], k = N(h) Wk, v = N(h) Wv [KV x dh]; an
  RMSNorm over dh on every head of q and of k; rotary positions (two halves,
  base rope_theta) on q and k in every layer; query head i reads KV head
  i // (H / KV); scores scaled by dh^-0.5.
  THE MASK: with block length B, position i attends position j iff
  j // B <= i // B: causal over blocks, both ways inside one, blocks counted
  from position 0 (the prompt's too). `forward` takes another mask, and the
  positions beside it, explicitly: the check's "clean sequence ; noised
  copy" pass has two tokens at one position.
  experts: p = softmax(N(a) Wr) over ALL `num_experts` in float32; the
  `num_experts_per_tok` largest chosen; w = the chosen p renormalised over
  them (`norm_topk_prob`); out = a + sum_chosen w_e Wdown,e(silu(Wgate,e x)
  * Wup,e x). No shared expert, no selection bias, every layer sparse.
  the logits of a position are over ITS OWN token: nothing is shifted.

`generate` is the published loop without a cache, a block at a time: the
block starts as the prompt's tail and `mask_token_id` elsewhere; every
denoising forward is a FULL forward over the sequence so far, takes each
masked position's best token and its probability (the confidence), and
unmasks the `ceil(B / steps)` most confident (ties to the earlier position)
and every one over `threshold`; an unmasked token is final; a block with
nothing masked is done (a cache-less loop has no commit to make).

The base stays in the dtype it was made in (bfloat16 on the chip: the upcast
is exact) and is upcast a projection or an expert at a time inside jitted
stages, so that 4.36 B parameters (17.4 GB in float32) never stand in float32
at once; the stages compile once a sequence length.

The parameter tree is the harness's: embed/embedding [V, d], final_norm/scale,
lm_head/kernel [d, V], and the layers either unrolled (`block_<i>`) or as
`blocks`, a tuple of the layers' trees or one stacked tree. A layer:
{RMSNorm_0, RMSNorm_1}/scale [d], {q_norm, k_norm}/scale [dh], {wq, wk, wv,
wo}/kernel, moe/router/kernel [d, E], moe/experts_{w_gate, w_up,
w_down}/kernel [E, in, out].
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import HI, rounder


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, pos, base):
    """x [T, H, D], pos [T]: rotate the two halves of every head."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_params(params, i: int):
    """Layer i's parameters, whichever layout the tree is in."""
    if f"block_{i}" in params:
        return params[f"block_{i}"]
    blocks = params["blocks"]
    if isinstance(blocks, (tuple, list)):
        return blocks[i]
    return jax.tree.map(lambda a: a[i], blocks)


def block_mask(t: int, block: int) -> np.ndarray:
    """[T, T] bool: i attends j iff j // block <= i // block."""
    at = np.arange(t) // block
    return at[None, :] <= at[:, None]


def expert_layer(x, moe, m: dict, mm):
    """sum over the chosen experts of w_e E_e(x), x [T, d] normed rows: every
    expert in turn over all rows, weighted 0 where it was not chosen."""
    p = jax.nn.softmax(jnp.matmul(x, _f32(moe["router"]["kernel"]),
                                  precision=HI), axis=-1)            # [T, E]
    _, chosen = jax.lax.top_k(p, m["num_experts_per_tok"])
    w = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], chosen].set(
        jnp.take_along_axis(p, chosen, axis=-1))
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(acc, e):
        gate, up, down, g = e
        y = mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        moe["experts_w_gate"]["kernel"], moe["experts_w_up"]["kernel"],
        moe["experts_w_down"]["kernel"], w.T))
    return out


@functools.lru_cache(maxsize=None)
def _stages(model_json: str, precision: str) -> dict:
    """The jitted pieces of one forward, for one model and precision."""
    m = json.loads(model_json)
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(_f32(w)), precision=HI)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    dh, eps = m["head_dim"], m["rms_norm_eps"]
    base = float(m["rope_theta"])

    @jax.jit
    def layer(bl, x, mask, pos):
        t = x.shape[0]
        h = _rms_norm(x, bl["RMSNorm_0"]["scale"], eps)
        q = mm(h, bl["wq"]["kernel"]).reshape(t, heads, dh)
        k = mm(h, bl["wk"]["kernel"]).reshape(t, kv, dh)
        v = mm(h, bl["wv"]["kernel"]).reshape(t, kv, dh)
        q = _rope(_rms_norm(q, bl["q_norm"]["scale"], eps), pos, base)
        k = _rope(_rms_norm(k, bl["k_norm"]["scale"], eps), pos, base)
        qg = rnd(q).reshape(t, kv, heads // kv, dh)
        s = jnp.einsum("qkgd,skd->kgqs", qg, rnd(k),
                       precision=HI) * dh ** -0.5
        a = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", rnd(a), rnd(v), precision=HI)
        x = x + mm(o.reshape(t, heads * dh), bl["wo"]["kernel"])
        return x + expert_layer(
            _rms_norm(x, bl["RMSNorm_1"]["scale"], eps), bl["moe"], m, mm)

    @jax.jit
    def head(x, scale, kernel):
        return mm(_rms_norm(x, scale, eps), kernel)

    return {"layer": layer, "head": head}


def forward(params, tokens, model: dict, precision: str = "f32",
            adapters=None, mask=None, positions=None, rows=None):
    """Logits [T, V] of ONE sequence of token ids [T], each over its own
    position's token; `rows` keeps only those positions' logits. `mask`
    [T, T] (i attends j where true; default the block-causal mask of
    `block_length`) and `positions` [T] (default 0 .. T - 1) say otherwise
    for the check's passes."""
    if adapters:
        raise NotImplementedError("the serving reference takes no adapters")
    st = _stages(json.dumps(model, sort_keys=True), precision)
    t = int(tokens.shape[0])
    mask = jnp.asarray(block_mask(t, model["block_length"])
                       if mask is None else mask)
    pos = jnp.arange(t) if positions is None else jnp.asarray(positions)
    x = _f32(jnp.take(params["embed"]["embedding"], jnp.asarray(tokens),
                      axis=0))
    for i in range(model["num_hidden_layers"]):
        x = st["layer"](layer_params(params, i), x, mask, pos)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return st["head"](x, params["final_norm"]["scale"],
                      params["lm_head"]["kernel"])


def unmask(conf: np.ndarray, masked: np.ndarray, steps: int,
           threshold) -> np.ndarray:
    """Which of a block's masked positions one denoising forward unmasks:
    the ceil(B / steps) most confident (ties to the earlier position), and
    every one over `threshold` (None: the static rule)."""
    least = -(-len(conf) // steps)
    order = sorted(np.nonzero(masked)[0], key=lambda j: (-conf[j], j))
    pick = np.zeros(len(conf), bool)
    pick[order[:least]] = True
    if threshold is not None:
        pick |= masked & (conf > threshold)
    return pick


def generate(params, prompt: list, max_new: int, model: dict, steps=None,
             threshold=None, eos=None, precision: str = "f32"):
    """The published loop without a cache, greedy. -> (tokens, notes): the
    `max_new` (fewer after an `eos`) generated tokens in order of position,
    and beside each the (index within its block of the forward that unmasked
    it, its confidence then)."""
    block, mask_id = model["block_length"], model["mask_token_id"]
    steps = block if steps is None else steps
    plen = len(prompt)
    start = plen // block * block
    seq = list(prompt[:start])
    out, notes = [], []
    while True:
        tail = list(prompt[start:start + block])
        toks = np.array(tail + [mask_id] * (block - len(tail)))
        masked = np.arange(block) >= len(tail)
        seen = {}
        f = 0
        while masked.any():
            ids = jnp.asarray(np.concatenate([np.array(seq, np.int64), toks]
                                             ).astype(np.int32))
            logits = np.asarray(forward(
                params, ids, model, precision,
                rows=np.arange(len(seq), len(seq) + block)), np.float64)
            best = logits.argmax(-1)
            lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(
                -1)) + logits.max(-1)
            conf = np.exp(logits[np.arange(block), best] - lse)
            pick = unmask(np.where(masked, conf, -1.0), masked, steps,
                          threshold)
            for j in np.nonzero(pick)[0]:
                toks[j], seen[int(j)] = best[j], (f, float(conf[j]))
            masked &= ~pick
            f += 1
        for j in range(block):
            p = start + j
            if plen <= p < plen + max_new:
                out.append(int(toks[j]))
                notes.append(seen[j])
                if eos is not None and toks[j] == eos:
                    return out, notes
        seq += [int(v) for v in toks]
        start += block
        if start >= plen + max_new:
            return out, notes
