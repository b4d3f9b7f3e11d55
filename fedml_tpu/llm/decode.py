"""KV-cache greedy decoding for TransformerLM — the serving hot path.

(reference: the FedLLM spotlight serves through HF transformers' generate(),
whose KV cache is the standard autoregressive optimization; this is the
TPU-native equivalent for this repo's LLaMA-shaped model.)

Why a hand-written functional decode instead of flax mutable cache
collections: the forward must (a) run over the SCAN-LAYERS stacked param
layout (each lax.scan step's products read their layer of the [L, ...]
leaves in place — the layout the 7B in-scan training path uses,
llm/quant.py; the engine holds wq/wk/wv as one kernel so that their product
does too, `lay_out_blocks`), (b) accept int8-quantized
{q, s} leaves with per-layer dequant, and (c) keep every shape static so
one compiled program serves every request. The body math mirrors
quant.make_inscan_quant_apply (RMSNorm → RoPE causal MHA → SwiGLU,
bias-free kernels) with attention specialized to the decode shapes:

- prefill: one full forward over the prompt that also EMITS each layer's
  roped K/V (scan ys) into a fixed-size [L, B, max_len, H, Dh] cache;
- step: one token — each layer attends its fresh roped q against the
  cached K/V (masked at positions > pos), writes its own K/V at pos, and
  the layer scan threads the cache through as scanned inputs/outputs.

That pair (`make_kv_decode`) is the PER-REQUEST path: a cache per call,
sized to the request, inside `make_generate`'s one program — and the
oracle the engine's tests compare against. The continuous-batching engine
(serving/engine.py) runs `make_paged_kv_decode`'s four programs over one
persistent pool of KV pages, or, for a model of latent-attention layers
(llm/latent.py; a dense or an expert feed-forward a layer),
`make_paged_latent_decode`'s four over a pool of latent rows with no heads
axis. `unserved(model)` says what neither set runs, `engine_only(model)`
what of the rest the per-request pair cannot.

Per-token cost drops from O(T·D²) (full recompute of every position's
projections) to O(D² + T·D): at max_len=256 that is ~two orders of
magnitude fewer projection FLOPs per generated token.

Parity is pinned against the full-recompute forward in
tests/test_kv_decode.py for both f32 and int8 bases, with and without
LoRA adapters.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..parallel.seq import _NEG, dense_causal_attention
from .quant import (
    QKV, dequant_leaf, lm_head_logits, merged_kernel, project_qkv, rms_norm,
    split_adapters, swiglu_mlp,
)

Pytree = Any


def layer_scope(part: str):
    """`decode.<part>` as a `jax.named_scope`: the names a device trace
    shows for the decode programs' layers (kv_write, attn, mlp, head, and in
    the latent programs index and moe; the engine adds sample). What a layer
    scan itself does (reading its layer of the stacked weights and of the
    pool) is left outside every scope on purpose: it reads as the
    program's time under no layer (PERF.md section 3). One place for the
    names, so a single block body inherits them."""
    return jax.named_scope(f"decode.{part}")


def unserved(model) -> list:
    """What a TransformerLM has that no decode program here can run, one
    sentence a mechanism. Two sets of programs exist: the block of per-head
    keys and values (`make_paged_kv_decode`: grouped or as many KV heads as
    heads, per-head q/k norms, full layers with or without rotary positions
    beside window layers that attend the last `window` positions through a
    ring of pages a slot, a SwiGLU or the expert layer (its router reading
    the feed-forward's input or the attention's), causal or a diffusion
    block's mask; the model's own norm eps and rope base) and the LATENT
    block's (`make_paged_latent_decode`: latent attention under its indexer
    in EVERY layer, a SwiGLU or the expert layer a layer);
    llm/transformer.py's block trains more than they serve."""
    out = []
    if not hasattr(model, "kinds"):     # no TransformerLM: nothing to depart in
        return out
    latent = [a == "latent" for a, _ in model.kinds]
    if any(latent) and not all(latent):
        out.append(
            "latent layers beside layers of per-head keys and values: a "
            "page pool holds ONE kind of row (serving/engine.py allocates "
            "`kv` and `ik` leaves or `k` and `v` leaves, never both)")
    return out


def engine_only(model) -> str:
    """Why the PER-REQUEST pair (`make_kv_decode`, and `GreedyLMPredictor`
    without `decode_slots`) cannot run a model the engine's programs can, or
    "": that pair is the dense block's alone (as many KV heads of d_model /
    n_heads as heads, no q/k norm, a SwiGLU, every layer full attention
    with rotary positions, one causal token a step). Window layers and full
    layers without rotary positions are the engine's alone: its cache is a
    page pool in which a window layer keeps a ring of pages a slot."""
    if not hasattr(model, "kinds"):
        return ""
    if getattr(model, "latent", None) is not None:
        return "latent attention"
    if getattr(model, "diffusion_block", 0):
        return "generation by diffusion over blocks"
    out = []
    if not model.rope_full or any(a == "window" for a, _ in model.kinds):
        out.append("window layers or full layers without rotary positions")
    dense = ((model.n_kv_heads or model.n_heads) == model.n_heads
             and (model.head_dim or model.d_model // model.n_heads)
             * model.n_heads == model.d_model and not model.qk_norm
             and not any(f == "moe" for _, f in model.kinds))
    if not dense:
        out.append("grouped KV heads, per-head q/k norms or experts")
    return "; ".join(out)


def require_servable(model) -> None:
    """Refuse, by the mechanism lacking, a model the decode programs
    cannot run (a drafting head for self-speculation is none of theirs
    either: `spec_decode` drafts from the request's own history)."""
    lacking = unserved(model)
    if lacking:
        raise NotImplementedError(
            "this TransformerLM cannot be served yet; the decode path "
            "lacks: " + "; ".join(lacking))


def stack_blocks(params: Pytree, n_layers: int,
                 unrolled: bool = False) -> Pytree:
    """Convert an UNROLLED TransformerLM param tree (block_0..block_{L-1})
    to the layout the decode path consumes: `{"blocks": [L, ...]}`, every
    leaf stacked on a leading layer axis, where every layer has the same
    parameters and no experts (the dense block's programs scan over it),
    and a TUPLE of the layers' trees as they are where they differ in kind
    (a dense feed-forward, then expert layers) or hold experts: such layers
    run unrolled, each layer's weights read where they lie (under a scan
    the compiler may copy a layer's slice out of the stack before a product
    reads it, and an expert layer's weights are a gigabyte; PERF.md section
    6). Nothing is copied for the tuple, so a tree that fills half the
    device still fits. `unrolled` asks for the tuple whatever the layers
    hold (layers of different attention kinds: window and full). Trees
    already in either layout pass through unchanged, but for a stack asked
    for `unrolled`; `lay_out_blocks` gives the engine's form of the
    stack."""
    if "blocks" in params:
        if unrolled and not isinstance(params["blocks"], (tuple, list)):
            return {**params, "blocks": tuple(block_layers(params["blocks"]))}
        return params
    blocks = [params[f"block_{i}"] for i in range(n_layers)]
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    if unrolled or len({jax.tree.structure(b) for b in blocks}) > 1 or any(
            "moe" in b for b in blocks):
        out["blocks"] = tuple(blocks)
        return out
    from ..ops.tree import tree_stack

    out["blocks"] = tree_stack(blocks)
    return out


def lay_out_blocks(params: Pytree, groups: int = 1) -> Pytree:
    """The decode engine's form of a STACKED tree (`stack_blocks`' dense
    layout): wq, wk and wv become ONE kernel `wqkv` [L, d, G, C] that the
    programs' q/k/v product reads in place (`quant.fused_qkv`). Group g of
    the G holds its share of each projection's columns, `[q_g | k_g |
    v_g]`, so an `mp` mesh of G devices splits the G axis and each device
    keeps the columns the column-split kernels gave it
    (`partition.transformer_lm_rules`). Int8 `{q, s}` leaves are laid out
    alike. The three separate kernels cost the layer scan a copy of each
    layer's slice and a layout change before every product: on the TPU the
    product splits its output into heads, the compiler then wants each
    kernel [heads, Dh, d], and a slice of the stack is neither (PERF.md
    section 6). A tuple of layers, a tree laid out already, or
    kernels of differing forms or widths not divisible by G pass through
    unchanged."""
    blocks = params.get("blocks")
    if not isinstance(blocks, dict) or any(
            set(blocks.get(n, ())) != {"kernel"} for n in QKV):
        return params
    kernels = [blocks[n]["kernel"] for n in QKV]
    forms = [sorted(k) if isinstance(k, dict) else None for k in kernels]
    if forms[1:] != forms[:-1] or any(
            jax.tree.leaves(k)[0].shape[-1] % groups for k in kernels):
        return params

    def fuse(leaves):
        return jnp.concatenate(
            [a.reshape(a.shape[:-1] + (groups, -1)) for a in leaves], -1)

    out = {k: v for k, v in blocks.items() if k not in QKV}
    out["wqkv"] = {"kernel": jax.tree.map(lambda *a: fuse(a), *kernels)}
    return {**params, "blocks": out}


def block_layers(blocks) -> list:
    """The layers' parameter trees, one a layer, of either layout
    `stack_blocks` gives."""
    if isinstance(blocks, (tuple, list)):
        return list(blocks)
    n = jax.tree.leaves(blocks)[0].shape[0]
    return [jax.tree.map(lambda a: a[i], blocks) for i in range(n)]


def stack_adapter_blocks(adapters: Optional[Pytree],
                         n_layers: int) -> Optional[Pytree]:
    """Convert UNROLLED-layout LoRA adapter keys (block_0/wq/kernel ...)
    to the stacked form (blocks/wq/kernel with a leading [L] axis) that
    split_adapters consumes. Stacked/None/top-level-only trees pass
    through. Without this, unrolled adapter keys would miss the 'blocks/'
    prefix and be SILENTLY ignored by the decode path."""
    if not adapters or not any(k.startswith("block_0/") for k in adapters):
        return adapters
    from ..ops.tree import tree_stack

    out = {k: v for k, v in adapters.items()
           if not (k.startswith("block_") and k.split("/", 1)[0][6:].isdigit())}
    suffixes = sorted(k.split("/", 1)[1] for k in adapters
                      if k.startswith("block_0/"))
    for suf in suffixes:
        try:
            parts = [adapters[f"block_{i}/{suf}"] for i in range(n_layers)]
        except KeyError as e:
            raise ValueError(
                f"adapter tree adapts {suf!r} on some layers but not "
                f"{e.args[0]!r} — per-layer-uniform adapters are required "
                "to stack into the scan layout") from None
        out[f"blocks/{suf}"] = tree_stack(parts)
    return out


def _batched_keys(key) -> bool:
    """True iff `key` is a [B] TYPED key array (per-row rng streams).
    Shape truthiness alone would misroute a legacy uint32[2] PRNGKey —
    ndim 1 but not a key array — into the vmap path and crash."""
    return key.ndim == 1 and jnp.issubdtype(key.dtype, jax.dtypes.prng_key)


def _rope_rows(x, pos_rows, base: float = 10000.0):
    """transformer.rope generalized to PER-ROW positions: x [B, T, H, D],
    pos_rows [B, T] — identical math (angles = pos·freqs, rotate halves),
    just with a batched angle table, so batched decode rows at different
    global positions share one program."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos_rows[..., None].astype(jnp.float32) * freqs   # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _block_math(dtype, eps: float, alpha: float):
    """The dense block's math (llm/quant.py — one implementation, shared
    with the in-scan training forward) bound to a decode factory's dtype /
    eps / alpha. Both factories below unpack the same seven closures:
    (norm, dq, merged, qkv, mlp, head, split_ads)."""

    def norm(x, scale):
        return rms_norm(x, scale, eps)

    def dq(leaf):
        return dequant_leaf(leaf, dtype)

    def merged(bl, ad_l, name, rank_scale):
        return merged_kernel(bl, ad_l, name, rank_scale, dtype)

    def qkv(bl, ad_l, rank_scale, h, n_hd, head_dim=None):
        return project_qkv(bl, ad_l, rank_scale, h, n_hd, dtype, head_dim)

    def mlp(bl, ad_l, rank_scale, x):
        return swiglu_mlp(bl, ad_l, rank_scale, x, dtype, eps)

    def head(params, top_ads, rank_scale, x):
        return lm_head_logits(params, top_ads, rank_scale, x, dtype, eps)

    def split_ads(adapters):
        return split_adapters(adapters, alpha)

    return norm, dq, merged, qkv, mlp, head, split_ads


def make_kv_decode(n_heads: int, alpha: float = 16.0,
                   dtype=jnp.float32, eps: float = 1e-6,
                   prefill_attn_fn=None):
    """Returns (prefill, step) over scan-layout params (float or int8
    {q, s} leaves; `adapters` is a llm.lora tree or None).

    prefill(params, adapters, tokens, max_len)
        -> (cache, logits_last)   # tokens [B, T_prompt]; cache k/v
                                  # [L, B, max_len, H, Dh]
    step(params, adapters, cache, pos, token)
        -> (cache, logits)        # token [B] at global position `pos`

    prefill_attn_fn swaps the prompt pass's attention (default dense
    causal) — pass ops.flash_attention.flash_attn_fn for long prompts,
    where the O(T²) dense materialization is the prefill bottleneck; the
    decode steps are unaffected (their attention is a masked [1, T]
    row against the cache, already O(T))."""
    from .transformer import rope

    prefill_attn = prefill_attn_fn or dense_causal_attention

    norm, dq, merged, qkv, mlp, head, split_ads = _block_math(
        dtype, eps, alpha)

    def prefill(params, adapters, tokens, max_len: int, length=None):
        """tokens may be right-PADDED to a fixed bucket; `length` (traced
        ok) is the real prompt length — causal masking already keeps real
        positions from attending padded ones (padding is strictly future),
        padded positions' K/V entries are masked in step() until a real
        decode token overwrites them, and the returned logits are read at
        position length-1. length=None means tokens are exactly the
        prompt (the static-shape path)."""
        blk_ads, top_ads, rank_scale = split_ads(adapters)
        emb = dq(params["embed"]["embedding"])
        x = emb[tokens]
        b, t = tokens.shape
        pos = jnp.arange(t)

        def body(x, layer):
            bl, ad_l = layer
            h = norm(x, dq(bl["RMSNorm_0"]["scale"]))
            q, k, v = qkv(bl, ad_l, rank_scale, h, n_heads)
            q, k = rope(q, pos), rope(k, pos)
            o = prefill_attn(q, k, v)
            x = x + o.reshape(x.shape[:2] + (-1,)) @ merged(
                bl, ad_l, "wo", rank_scale)
            x = mlp(bl, ad_l, rank_scale, x)
            # emit the roped K and raw V padded to the cache length
            pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
            return x, (jnp.pad(k, pad), jnp.pad(v, pad))

        x, (ck, cv) = jax.lax.scan(body, x, (params["blocks"], blk_ads))
        if length is None:
            last = x[:, -1]
        else:
            # per-row real lengths (a scalar broadcasts): each row's last
            # REAL position feeds the head — batched prompts of different
            # lengths share one program
            lengths = jnp.broadcast_to(
                jnp.asarray(length, jnp.int32), (x.shape[0],))
            last = jax.vmap(lambda xi, li: jax.lax.dynamic_index_in_dim(
                xi, li - 1, axis=0, keepdims=False))(x, lengths)
        logits = head(params, top_ads, rank_scale, last[:, None])
        return {"k": ck, "v": cv}, logits[:, 0]

    def step(params, adapters, cache, pos, token):
        blk_ads, top_ads, rank_scale = split_ads(adapters)
        emb = dq(params["embed"]["embedding"])
        x = emb[token][:, None, :]                       # [B, 1, D]
        max_len = cache["k"].shape[2]
        # pos: per-row write positions [B] (a scalar broadcasts) — batched
        # rows decode at DIFFERENT global positions
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                               (token.shape[0],))

        def body(x, layer):
            bl, ad_l, ck, cv = layer                     # ck/cv [B,S,H,Dh]
            h = norm(x, dq(bl["RMSNorm_0"]["scale"]))
            q, k, v = qkv(bl, ad_l, rank_scale, h, n_heads)
            q = _rope_rows(q, pos[:, None])
            k = _rope_rows(k, pos[:, None])
            write = jax.vmap(lambda c, kk, p: jax.lax.dynamic_update_slice(
                c, kk, (p, 0, 0)))
            ck = write(ck, k, pos)
            cv = write(cv, v, pos)
            scale = q.shape[-1] ** -0.5
            s = jnp.einsum("bqhd,bkhd->bhqk", q, ck) * scale
            # causal + unfilled, per row
            live = jnp.arange(max_len)[None] <= pos[:, None]       # [B,S]
            s = jnp.where(live[:, None, None, :], s, _NEG)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), cv)
            x = x + o.reshape(x.shape[:2] + (-1,)) @ merged(
                bl, ad_l, "wo", rank_scale)
            x = mlp(bl, ad_l, rank_scale, x)
            return x, (ck, cv)

        x, (ck, cv) = jax.lax.scan(
            body, x, (params["blocks"], blk_ads, cache["k"], cache["v"]))
        logits = head(params, top_ads, rank_scale, x)
        return {"k": ck, "v": cv}, logits[:, 0]

    return prefill, step


def _kv_quant_write(pool, scales, wpage, woff, vals):
    """Quantize-at-write for the int8 KV pool: symmetric per-(page, head)
    scales that only GROW within one page tenancy (running max). pool
    [P, ps, H, Dh] int8, scales [P, H] f32, wpage/woff [...] page/offset
    indices, vals [..., H, Dh] new K or V rows in the compute dtype.

    Four scatters, sound under append-only pages and duplicate page
    indices within one call:
      0. a write at offset 0 BEGINS a page (slot positions are monotone
         and page-aligned, so offset 0 is written exactly when a page is
         freshly claimed — including a post-rollback rewrite, whose old
         rows were rejected speculation): scatter-min the previous
         tenant's scale to 0 first. Without this, scales would only ever
         grow across a server's lifetime — one outlier from a
         long-retired request would pin a reused page's resolution
         forever, and decoded tokens would depend on page-allocation
         history (batched vs serial admission allocate in different
         orders and must stay token-identical);
      1. scatter-max each written row's |max|/127 into the touched pages'
         scales — duplicates fold associatively;
      2. requantize the RESIDENT rows of every touched page by
         s_old/s_new — the factor is exactly 1.0 when the scale did not
         grow, so round() is the identity and repeated writes to a page
         cost no accumulated error (rounding loss happens only the
         bounded number of times a page's running max actually
         increases); duplicate page indices write byte-identical values,
         so scatter order cannot matter (a freshly-reset page's factor
         is 0 — its stale resident rows are zeroed, and rows past the
         written range are read-masked anyway);
      3. quantize the new rows with the grown scale at their unique
         (page, offset) cells.
    Writes redirected to the null page 0 churn its scale with garbage —
    reads of page 0 only surface at masked-off positions, so that is
    inert by the same contract that makes the redirect safe."""
    f = vals.astype(jnp.float32)
    cand = jnp.max(jnp.abs(f), axis=-1) / 127.0            # [..., H]
    fresh = jnp.where((woff == 0)[..., None], 0.0, jnp.inf)
    scales = scales.at[wpage].min(fresh)
    s_new = scales.at[wpage].max(cand)
    so, sn = scales[wpage], s_new[wpage]                   # [..., H]
    snd = jnp.where(sn > 0, sn, 1.0)
    factor = jnp.where(sn > 0, so / snd, 1.0)
    resident = pool[wpage].astype(jnp.float32)             # [..., ps, H, Dh]
    requant = jnp.clip(jnp.round(resident * factor[..., None, :, None]),
                       -127, 127).astype(jnp.int8)
    pool = pool.at[wpage].set(requant)
    q = jnp.clip(jnp.round(f / snd[..., None]), -127, 127).astype(jnp.int8)
    pool = pool.at[wpage, woff].set(q)
    return pool, s_new


def make_paged_kv_decode(n_heads: int, page_size: int, alpha: float = 16.0,
                         dtype=jnp.float32, eps: float = 1e-6,
                         kernel: bool = False, mesh=None,
                         quant: bool = False, rope_base: float = 10000.0,
                         head_dim: Optional[int] = None,
                         qk_norm: bool = False, moe=None, block: int = 0,
                         attn_kinds: Optional[tuple] = None, window: int = 0,
                         rope_full: bool = True, ring: int = 0):
    """The decode engine's programs (serving/engine.py): K/V live in a
    persistent POOL of fixed-size pages `[L, n_pages, page_size, H, Dh]`,
    and each slot's logical sequence is described by an int32 page-table
    row mapping virtual position `t -> (row[t // page_size], t %
    page_size)`. Pages are what make the engine's HBM proportional to
    LIVE tokens (and let identical prompt prefixes share physical pages)
    rather than `slots x max_len`.

    Returns (chunk, step, verify, chunk_batch):

    chunk(params, adapters, cache, pages_row, tokens, t0, length)
        -> (cache, logits)     # ONE slot: process `length` prompt tokens
                               # (tokens [1, C] right-padded; length traced)
                               # at global positions t0..t0+length-1,
                               # writing their roped K / raw V into the
                               # slot's pages and attending against the
                               # gathered history + the chunk itself;
                               # logits [1, V] at position t0+length-1.
                               # Admission calls this repeatedly —
                               # chunked prefill — so a long prompt never
                               # occupies the device for more than one
                               # chunk between decode iterations.
    step(params, adapters, cache, pages, pos, token, active)
        -> (cache, logits)     # ALL slots one token: pages [S, max_pages],
                               # pos/token [S]. `active` REDIRECTS inactive
                               # slots' garbage K/V write to the reserved
                               # null page 0: an inactive slot's stale
                               # page-table entry may point at a page that
                               # was freed and re-allocated to ANOTHER
                               # slot, so its own old position is not a
                               # safe place to park the write.
    verify(params, adapters, cache, pages, pos, tokens, active)
        -> (cache, logits)     # ALL slots, C tokens each (tokens
                               # [S, C] at positions pos..pos+C-1;
                               # logits [S, C, V]) — the speculative-
                               # decoding target forward: slot s's
                               # query i attends everything <= pos[s]+i
                               # INCLUDING this call's own K/V writes
                               # at pos..pos+i, so logits[s, i] is the
                               # true next-token distribution exactly
                               # when tokens[s, 1..i] matched the
                               # target's own picks (the greedy-exact
                               # acceptance rule). Writes past the
                               # slot's page-table reservation redirect
                               # to the null page; step IS verify at
                               # C == 1.
    chunk_batch(params, adapters, cache, pages, tokens, t0, lengths)
        -> (cache, logits)     # BATCHED admission prefill: B same-bucket
                               # requests' chunks through ONE program
                               # (engine admit_batch > 1). tokens [B, C]
                               # right-padded per row, pages [B,
                               # max_pages], t0/lengths [B]; logits
                               # [B, V] at each row's t0 + length - 1 —
                               # exactly chunk's last-position logits.
                               # length 0 marks a PAD row: every write
                               # redirects to the null page and its
                               # logits row is garbage the caller
                               # discards. Keeps the gather path like
                               # chunk — prefill cost amortizes over the
                               # prompt; the fused kernel stays the
                               # decode-side hot path.

    `quant=True` stores the pool in int8 with per-(page, head) f32
    scales riding as extra cache leaves {"ks", "vs"} [L, P, H]:
    quantize-at-write with running-max scales (_kv_quant_write),
    dequantize at every gather — and inside the Pallas kernel, where
    the scales arrive as page-table-indexed operands so the pool stays
    int8 all the way into VMEM. Halves persistent KV HBM (the slot
    ceiling) for a <1pt greedy-token quality delta; `quant=False` is
    byte-identical to the pre-quant layout.

    How the pool is carried: the cache the engine holds keeps its
    layout `[L, P, page, H, Dh]` (scales `[L, P, H]`), and every program
    threads it through ONE layer scan (`scan_layers`) as part of the
    CARRY, viewed flat `[L * P, ...]`. Layer l writes its new rows at
    pages `l * P + id` and reads (gather or kernel) only the pages it
    attends to, so the donated pool is updated in place and a program
    moves the rows it touches, never the pool. The layer index rides the
    scan's xs beside the stacked weights.

    Page 0 is the null/trash page by contract, per layer (flat page
    `l * P`): never allocated to a request, it absorbs padded-position
    and inactive-slot writes; reads of it only ever surface at virtual
    positions beyond a slot's `pos`, which the live mask discards.
    Attention gathers each slot's pages into a virtually-contiguous
    [max_pages * page_size] sequence, so the math (and, pinned in tests,
    the greedy tokens) matches make_kv_decode's per-request cache — the
    gather is the XLA-level cost of paging; the win is that the
    PERSISTENT pool holds only `n_pages * page_size` rows.

    `kernel=True` swaps step/verify's gather-then-attend for the fused
    Pallas paged-attention kernel (ops/paged_attention.py) that reads
    each slot's pages IN PLACE via the device-side page table — no
    virtually-contiguous copy, and it walks only the pages that hold a
    position the slot's queries attend (from `pos`, C and `active`: a
    retired slot costs no page read), where the gather moves every
    slot's whole table row whatever is live. chunk (prefill) keeps the
    gather: its cost is amortized over the whole prompt and the kernel is
    the decode-side hot path. `mesh` (with an `mp` axis) shard_maps the
    kernel over the heads axis — the same layout
    partition.paged_kv_cache_spec pins on the pool, reaching the kernel
    with zero resharding. Token identity vs the gather path is pinned in
    tests/test_decode_kernel_spec.py. `eps` and `rope_base` are the
    model's own (`norm_eps`, `rope_base`).

    Where the model departs from the dense block (each default lowers to
    the dense block's programs, text for text): heads are `head_dim` wide
    and the pool holds as many KV heads as wk's width has of them (the
    pool's heads axis says how many; grouped heads share a KV head's pages,
    in the gather and in the kernel); `qk_norm` is an RMSNorm over each head
    of q and k before the rotary positions; `moe` (a llm.moe.MoE) puts the
    expert layer in the SwiGLU's place in every layer whose parameters hold
    one, over the LIVE rows alone (an idle slot's and a chunk's padded rows
    are routed nowhere), and every program then returns a third value, the
    layers' folded counters (`moe_pairs`, `moe_experts_live`,
    `moe_tile_rows`, ...). Layers that `stack_blocks` left as a tuple run
    unrolled.

    `block` = B > 0 is a block-diffusion model: position i attends j iff
    j // B <= i // B. `chunk` and `chunk_batch` take that mask (t0 and every
    length a whole number of blocks), and `verify` is the WINDOW program:
    C == B tokens at a block-aligned `pos`, the window's own keys visible to
    all its queries, the logits over each position's OWN token, the block's
    K/V written at every call (a later call or the commit overwrites them
    before any later block reads them). `step` has no meaning then.

    `attn_kinds` (one "full" or "window" a layer; None: every layer full)
    with `window` = W and `ring` = R: a "window" layer's query at position
    i attends the keys i - W + 1 .. i, and the layer keeps no page row of
    the slot's table. Its keys and values live in a pool of their own, the
    cache leaves `wk` / `wv` `[L_w, 1 + S * R, page_size, KV, Dh]` (L_w
    window layers, page 0 the null page again), and slot s owns the RING
    of pages `1 + s * R .. (s + 1) * R`: virtual page p of the slot lies at
    ring page `p % R`, reused as the slot advances. R is at least
    `ceil((W + C) / page_size) + 1` for the widest C queries a program
    runs (a prefill chunk), so the pages a program reads and writes (the
    window of its first query to its last query) never meet modulo R; a
    slot holds R pages whatever its context. The `k` / `v` leaves hold the
    other layers only (`[L - L_w, P, ...]`), and such layers run unrolled.
    `chunk` and `chunk_batch` then take the slot (slots) the chunk is
    for, as a further argument. `rope_full=False`: full layers take no
    rotary positions (window layers always do). `moe.route_from == "attn"`
    routes each token by the attention norm's output, read before
    attention."""
    ps = int(page_size)
    # per layer: whether it is a window layer (None: every layer full)
    windowed = (tuple(a == "window" for a in attn_kinds)
                if attn_kinds is not None else None)
    R = int(ring) if windowed and any(windowed) else 0
    if R and (not window or quant or mesh is not None):
        raise NotImplementedError(
            "window layers take `window` > 0 and keep bfloat16 ring pages "
            "on one chip: int8 ring pages and rings under a mesh are not "
            "built")
    norm, dq, merged, qkv, mlp, head, split_ads = _block_math(
        dtype, eps, alpha)
    if moe is not None:
        from .moe import COUNTERS, ExpertLayer, fold_counters

    def sees(kpos, qpos):
        """Whether a query at `qpos` attends the key at `kpos`."""
        return kpos // block <= qpos // block if block else kpos <= qpos

    def is_window(i) -> bool:
        """Whether layer i (None: a layer of the stack) is a window layer."""
        return i is not None and bool(windowed) and windowed[i]

    def rotated(i) -> bool:
        """Whether layer i takes rotary positions: every window layer, and
        full layers if `rope_full`."""
        return rope_full or is_window(i)

    def router_input(h):
        """What a router that reads the attention norm's output is handed."""
        return h if moe is not None and moe.route_from == "attn" else None

    def project(bl, ad_l, rank_scale, h, posr, rotate=True):
        """Roped q [B, C, H, Dh], roped k and raw v [B, C, KV, Dh] of `h`
        at positions `posr` [B, C] (or [C]: a chunk's one row); q and k
        unrotated where `rotate` is False."""
        q, k, v = qkv(bl, ad_l, rank_scale, h, n_heads, head_dim)
        if qk_norm:
            q = norm(q, dq(bl["q_norm"]["scale"]))
            k = norm(k, dq(bl["k_norm"]["scale"]))
        if not rotate:
            return q, k, v
        # a chunk's row is lifted where it is used, once for q and once
        # for k, as the dense block's program always had it
        rows = lambda: posr if posr.ndim == 2 else posr[None, :]
        return (_rope_rows(q, rows(), rope_base),
                _rope_rows(k, rows(), rope_base), v)

    def attend_grouped(q, kk, vv, posr, kpos=None, span=None):
        """q [B, C, H, Dh] at positions `posr` [B, C] over gathered kk/vv
        [B, T, KV, Dh], query head i reading KV head i // (H / KV). `kpos`
        [B, T]: the virtual position of each gathered row (None: its row;
        negative: never written), and then a query at i attends only the
        keys j with i - j < `span` (None: every key it sees)."""
        b_, c, _, d = q.shape
        qg = q.reshape(b_, c, kk.shape[2], -1, d)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk) * d ** -0.5
        if kpos is None:
            live = sees(jnp.arange(kk.shape[1])[None, None, :],
                        posr[:, :, None])                    # [B, C, T]
        else:
            rel = posr[:, :, None] - kpos[:, None, :]        # [B, C, T]
            live = (kpos[:, None, :] >= 0) & (rel >= 0)
            if span is not None:
                live = live & (rel < span)
        s = jnp.where(live[:, None, None, :, :], s, _NEG)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1),
                          vv).reshape(q.shape)

    def attend(q, kk, vv, posr):
        """Attention of q [B, C, H, Dh] at positions `posr` [B, C] over a
        slot's gathered pages [B, T, KV, Dh]."""
        if kk.shape[2] != q.shape[2]:
            return attend_grouped(q, kk, vv, posr)
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
        live = (sees(jnp.arange(kk.shape[1])[None, None, :],
                     posr[:, :, None]))                      # [B, C, T]
        s = jnp.where(live[:, None, :, :], s, _NEG)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    def ring_pages(slots, vpage):
        """The ring page (of a window layer's pool, the layer's base left
        out) of slot `slots`'s virtual page `vpage`."""
        return 1 + slots * R + vpage % R

    def ring_view(pool, base, slots, last):
        """A window layer's gathered rings: K and V [B, R * page_size, KV,
        Dh] of slots `slots` [B] in ring order, and the virtual position of
        each row [B, R * page_size]: ring page r holds the latest virtual
        page <= `last` [B] that is r modulo R (negative: none yet)."""
        r = jnp.arange(R)
        kk, vv = kv_pages(pool, base + ring_pages(slots[:, None], r),
                          ("wk", "wv"))
        vpage = last[:, None] - (last[:, None] - r) % R          # [B, R]
        kpos = (vpage[:, :, None] * ps + jnp.arange(ps)).reshape(
            vpage.shape[0], -1)
        return kk, vv, kpos

    def ring_attend(pool, base, q, posr, slots, last_page):
        """A window layer's gathered attention of rows [B] of slots `slots`
        over their rings (`last_page` [B]: the latest virtual page
        written)."""
        kk, vv, kpos = ring_view(pool, base, slots, last_page)
        return attend_grouped(q, kk, vv, posr, kpos, window)

    def feed_forward(bl, ad_l, rank_scale, x, live, route_x=None):
        """-> (x, counters): the SwiGLU, or the expert layer over the
        `live` rows [B, C] with what it sows (its router reading `route_x`
        where it reads the attention norm's output)."""
        if moe is None or "moe" not in bl:
            with layer_scope("mlp"):
                return mlp(bl, ad_l, rank_scale, x), {}
        with layer_scope("moe"):
            h = norm(x, dq(bl["RMSNorm_1"]["scale"]))
            y, sown = ExpertLayer(moe).apply(
                {"params": bl["moe"]}, h, live, route_x, mutable=[COUNTERS])
            return x + y, fold_counters(sown[COUNTERS])

    def scan_layers(layer, x, params, blk_ads, cache):
        """THE layer scan of all four programs. The pool leaves ride the
        CARRY, each viewed flat as [L * P, ...] (a bitcast of the
        contiguous [L, P, ...] array), and every layer addresses its own
        pages at `base = l * P`: `layer(x, pool, base, bl, ad_l) ->
        (x, pool)` writes its new rows into the whole pool in place and
        reads only the pages it attends to. As scan `xs`/`ys` the pool
        was sliced out and restacked layer by layer, and the donated
        input copied once more to close the loop: three pool-sized moves
        a program for rows nobody read (PERF.md section 6, PR 27)."""
        n_layers, n_pages = cache["k"].shape[:2]
        pool = {name: leaf.reshape((leaf.shape[0] * leaf.shape[1],)
                                   + leaf.shape[2:])
                for name, leaf in cache.items()}

        def body(carry, xs):
            x, pool = carry
            bl, ad_l, l = xs
            x, pool, counted = layer(x, pool, l * n_pages, bl, ad_l)
            return (x, pool), counted

        if isinstance(params["blocks"], (tuple, list)):
            # layers `stack_blocks` left as they lie (experts): unrolled.
            # A window layer's base counts in the ring pool, any other's in
            # the page pool
            counted: dict = {}
            seen = [0, 0]
            for i, bl in enumerate(params["blocks"]):
                win = int(is_window(i))
                at, seen[win] = seen[win], seen[win] + 1
                x, pool, c = layer(
                    x, pool, at * (cache["wk"].shape[1] if win else n_pages),
                    bl, jax.tree.map(lambda a: a[i], blk_ads), i)
                counted = {k: counted.get(k, 0) + v for k, v in c.items()}
        elif R:
            raise ValueError("a model of window and full layers runs its "
                             "layers unrolled (stack_blocks(..., unrolled="
                             "True))")
        else:
            (x, pool), counted = jax.lax.scan(
                body, (x, pool), (params["blocks"], blk_ads,
                                  jnp.arange(n_layers, dtype=jnp.int32)))
            counted = {k: jnp.sum(v) for k, v in counted.items()}
        return x, {name: leaf.reshape(cache[name].shape)
                   for name, leaf in pool.items()}, counted

    def returns(cache, logits, counted):
        return (cache, logits, counted) if moe is not None else (
            cache, logits)

    def kv_write(pool, wpage, woff, k, v, names=("k", "v")):
        """New K/V rows into the flat pool at (wpage, woff); `wpage`
        already carries the layer's base, so the null page of layer l is
        base + 0 and absorbs that layer's redirected writes. `names`: the
        leaves written (`wk` / `wv`: a window layer's ring pool)."""
        with layer_scope("kv_write"):
            if quant:
                pk, ks = _kv_quant_write(pool["k"], pool["ks"], wpage, woff, k)
                pv, vs = _kv_quant_write(pool["v"], pool["vs"], wpage, woff, v)
                return {"k": pk, "v": pv, "ks": ks, "vs": vs}
            kn, vn = names
            return {**pool, kn: pool[kn].at[wpage, woff].set(k),
                    vn: pool[vn].at[wpage, woff].set(v)}

    def kv_pages(pool, idx, names=("k", "v")):
        """Gather the pages `idx` (base included) of the flat pool as a
        virtually-contiguous [..., len(idx) * page_size, H, Dh] K and V
        (int8 pools dequantize in place: scales[idx] [..., H] broadcast
        over the (page_size, Dh) axes)."""
        def one(leaf, scales):
            g = pool[leaf][idx]
            if quant:
                g = (g.astype(jnp.float32)
                     * pool[scales][idx][..., None, :, None]).astype(dtype)
            return g.reshape(idx.shape[:-1] + (-1,) + g.shape[-2:])
        return one(names[0], "ks"), one(names[1], "vs")

    def chunk(params, adapters, cache, pages_row, tokens, t0, length,
              slot=None):
        blk_ads, top_ads, rank_scale = split_ads(adapters)
        emb = dq(params["embed"]["embedding"])
        x = emb[tokens]                                   # [1, C, D]
        c = tokens.shape[1]
        j = jnp.arange(c)
        posr = jnp.asarray(t0, jnp.int32) + j             # [C] global pos
        length = jnp.asarray(length, jnp.int32)
        # padded tail positions (j >= length) write to the null page
        wpage = jnp.where(j < length, pages_row[posr // ps], 0)
        woff = posr % ps
        n_virt = pages_row.shape[0] * ps
        if R:
            slot = jnp.asarray(slot, jnp.int32)
            wring = jnp.where(j < length, ring_pages(slot, posr // ps), 0)
            last_page = (t0 + jnp.maximum(length, 1) - 1) // ps

        def layer(x, pool, base, bl, ad_l, i=None):
            win = is_window(i)
            with layer_scope("attn"):
                h = norm(x, dq(bl["RMSNorm_0"]["scale"]))
                q, k, v = project(bl, ad_l, rank_scale, h, posr, rotated(i))
            if win:
                pool = kv_write(pool, base + wring, woff, k[0], v[0],
                                ("wk", "wv"))
            else:
                pool = kv_write(pool, base + wpage, woff, k[0], v[0])
            with layer_scope("attn"):
                # gather AFTER the write so the chunk attends to itself;
                # page-table order makes the gathered view contiguous
                # virtual positions 0..n_virt-1
                if win:
                    o = ring_attend(pool, base, q, posr[None], slot[None],
                                    last_page[None])
                else:
                    kk, vv = kv_pages(pool, base + pages_row)
                    if kk.shape[1] != q.shape[2]:
                        o = attend_grouped(q, kk[None], vv[None],
                                           posr[None])
                    else:
                        scale = q.shape[-1] ** -0.5
                        s = jnp.einsum("bqhd,khd->bhqk", q, kk) * scale
                        live = sees(jnp.arange(n_virt)[None, :],
                                    posr[:, None])               # [C, T]
                        s = jnp.where(live[None, None, :, :], s, _NEG)
                        o = jnp.einsum("bhqk,khd->bqhd",
                                       jax.nn.softmax(s, -1), vv)
                x = x + o.reshape(x.shape[:2] + (-1,)) @ merged(
                    bl, ad_l, "wo", rank_scale)
            x, counted = feed_forward(bl, ad_l, rank_scale, x,
                                      (j < length)[None], router_input(h))
            return x, pool, counted

        x, cache, counted = scan_layers(layer, x, params, blk_ads, cache)
        with layer_scope("head"):
            last = jax.lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                                keepdims=False)
            logits = head(params, top_ads, rank_scale, last[None, None])
        return returns(cache, logits[:, 0], counted)

    if kernel:
        from ..ops.paged_attention import paged_attention

        def attn_fused(q, k_pool, v_pool, pages, pos, active, *scales):
            return paged_attention(q, k_pool, v_pool, pages, pos, *scales,
                                   active=active, causal=not block)

        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            # heads are independent in attention, so the mp split of the
            # pool (partition.paged_kv_cache_spec) reaches the kernel
            # as-is: each device runs it over its own heads, the page
            # table/positions/active mask replicated — no resharding, no
            # collective (the int8 scales split the same heads axis:
            # partition.paged_kv_scale_spec)
            heads = P(None, None, "mp", None)
            in_specs = (heads, heads, heads, P(None, None), P(None), P(None))
            if quant:
                in_specs += (P(None, "mp"), P(None, "mp"))
            attn_fused = jax.shard_map(
                attn_fused, mesh=mesh, in_specs=in_specs,
                out_specs=heads, check_vma=False)

    def verify(params, adapters, cache, pages, pos, tokens, active):
        """C tokens per slot through one forward (C = tokens.shape[1];
        C == 1 is the plain decode step). Query i of slot s sits at
        global position pos[s] + i; its K/V write lands there BEFORE
        attention, so the window attends to itself causally."""
        blk_ads, top_ads, rank_scale = split_ads(adapters)
        emb = dq(params["embed"]["embedding"])
        x = emb[tokens]                                   # [S, C, D]
        s_, c = tokens.shape
        if block and c != block:
            raise ValueError(
                f"a diffusion model's window is one block of {block} "
                f"tokens; got {c}")
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (s_,))
        posr = pos[:, None] + jnp.arange(c)               # [S, C]
        max_pages = pages.shape[1]
        rowidx = posr // ps
        # positions past the slot's page-table reservation (speculative
        # windows may overrun the token budget; those picks are
        # discarded) and inactive slots' writes both redirect to the
        # null page — a clamped row read could otherwise alias a REAL
        # page of this slot
        wpage = jnp.where(
            active[:, None] & (rowidx < max_pages),
            pages[jnp.arange(s_)[:, None], jnp.minimum(rowidx,
                                                       max_pages - 1)], 0)
        woff = posr % ps
        if R:
            slots = jnp.arange(s_)
            wring = jnp.where(active[:, None] & (rowidx < max_pages),
                              ring_pages(slots[:, None], rowidx), 0)
            last_page = (pos + c - 1) // ps
            ring_table = ring_pages(slots[:, None], jnp.arange(R))  # [S, R]

        def layer(x, pool, base, bl, ad_l, i=None):
            win = is_window(i)
            with layer_scope("attn"):
                h = norm(x, dq(bl["RMSNorm_0"]["scale"]))
                q, k, v = project(bl, ad_l, rank_scale, h, posr, rotated(i))
            if win:
                pool = kv_write(pool, base + wring, woff, k, v, ("wk", "wv"))
            else:
                pool = kv_write(pool, base + wpage, woff, k, v)
            with layer_scope("attn"):
                if kernel and win:
                    # the window kernel walks the slot's ring from the
                    # first query's window to its last query
                    o = paged_attention(q, pool["wk"], pool["wv"],
                                        base + ring_table, pos,
                                        active=active, window=window)
                elif kernel:
                    # fused path: this layer's pages read in place by the
                    # Pallas kernel, straight out of the carried pool — no
                    # virtually-contiguous copy materializes (int8 pools
                    # ride in as-is; the kernel dequants each slab in VMEM).
                    # `active` lets it skip a retired slot's stale row
                    scales = (pool["ks"], pool["vs"]) if quant else ()
                    o = attn_fused(q, pool["k"], pool["v"], base + pages,
                                   pos, active, *scales)
                elif win:
                    o = ring_attend(pool, base, q, posr, slots, last_page)
                else:
                    kk, vv = kv_pages(pool, base + pages)
                    o = attend(q, kk, vv, posr)
                x = x + o.reshape(x.shape[:2] + (-1,)) @ merged(
                    bl, ad_l, "wo", rank_scale)
            x, counted = feed_forward(
                bl, ad_l, rank_scale, x,
                jnp.broadcast_to(active[:, None], tokens.shape),
                router_input(h))
            return x, pool, counted

        x, cache, counted = scan_layers(layer, x, params, blk_ads, cache)
        with layer_scope("head"):
            logits = head(params, top_ads, rank_scale, x)
        return returns(cache, logits, counted)

    def step(params, adapters, cache, pages, pos, token, active):
        cache, logits, *counted = verify(params, adapters, cache, pages, pos,
                                         token[:, None], active)
        return (cache, logits[:, 0], *counted)

    def chunk_batch(params, adapters, cache, pages, tokens, t0, lengths,
                    slots=None):
        """Batched admission prefill (docstring above): verify-shaped
        positions (per-row t0), chunk-shaped write masking (tokens past
        a row's length — and PAD rows entirely — redirect to the null
        page), per-row last-live-position logits."""
        blk_ads, top_ads, rank_scale = split_ads(adapters)
        emb = dq(params["embed"]["embedding"])
        x = emb[tokens]                                   # [B, C, D]
        b_, c = tokens.shape
        j = jnp.arange(c)
        t0 = jnp.asarray(t0, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        posr = t0[:, None] + j[None, :]                   # [B, C]
        max_pages = pages.shape[1]
        rowidx = posr // ps
        wpage = jnp.where(
            (j[None, :] < lengths[:, None]) & (rowidx < max_pages),
            pages[jnp.arange(b_)[:, None],
                  jnp.minimum(rowidx, max_pages - 1)], 0)
        woff = posr % ps
        if R:
            # PAD rows' slot lies past the rings: their writes are dropped
            # above, their reads are garbage the engine discards
            slots = jnp.asarray(slots, jnp.int32)
            wring = jnp.where(
                (j[None, :] < lengths[:, None]) & (rowidx < max_pages),
                ring_pages(slots[:, None], rowidx), 0)
            last_page = (t0 + jnp.maximum(lengths, 1) - 1) // ps

        def layer(x, pool, base, bl, ad_l, i=None):
            win = is_window(i)
            with layer_scope("attn"):
                h = norm(x, dq(bl["RMSNorm_0"]["scale"]))
                q, k, v = project(bl, ad_l, rank_scale, h, posr, rotated(i))
            if win:
                pool = kv_write(pool, base + wring, woff, k, v, ("wk", "wv"))
            else:
                pool = kv_write(pool, base + wpage, woff, k, v)
            with layer_scope("attn"):
                if win:
                    o = ring_attend(pool, base, q, posr, slots, last_page)
                else:
                    kk, vv = kv_pages(pool, base + pages)
                    o = attend(q, kk, vv, posr)
                x = x + o.reshape(x.shape[:2] + (-1,)) @ merged(
                    bl, ad_l, "wo", rank_scale)
            x, counted = feed_forward(bl, ad_l, rank_scale, x,
                                      j[None, :] < lengths[:, None],
                                      router_input(h))
            return x, pool, counted

        x, cache, counted = scan_layers(layer, x, params, blk_ads, cache)
        # per-row last live position (PAD rows clamp to 0 — garbage the
        # engine discards alongside their dropped scatters)
        with layer_scope("head"):
            last = jax.vmap(lambda xr, n: jax.lax.dynamic_index_in_dim(
                xr, jnp.maximum(n, 1) - 1, axis=0, keepdims=False))(
                    x, lengths)
            logits = head(params, top_ads, rank_scale, last[:, None])
        return returns(cache, logits[:, 0], counted)

    return chunk, step, verify, chunk_batch


LATENT_ADAPTERS = (
    "LoRA adapters on a latent-attention model: the decode programs merge "
    "adapters into wq/wk/wv/wo and the SwiGLU (llm/quant.py merged_kernel) "
    "and know no low-rank projection; merge them first (llm.lora.lora_merge)")


def make_paged_latent_decode(model, page_size: int, dtype=jnp.float32):
    """`make_paged_kv_decode`'s four programs for a model whose layers are
    latent attention under an indexer's selection (llm/latent.py), with a
    dense or an expert feed-forward (llm/moe.py) a layer. Same arguments,
    same page table, same null page 0, same returns.

    The pool has NO heads axis: a token is ONE row a layer, in two leaves,
    `kv` `[L, P, page, width]` holding `c_kv || k_rope` (padded to whole
    lanes: `Latent.width`) and `ik` `[L, P, page, index_dim]` holding the
    indexer's key. Both are threaded through the layers flat
    (`[L * P, ...]`, layer l at pages `l * P + id`, as the K/V pool is), and
    a prefix page brings both back.

    One body for all four programs (a chunk is a batch of one): the new
    rows are written, then attention runs in the ABSORBED form over the
    slot's pages in place: `ops.paged_attention.index_scores` walks the
    live pages of `ik` for the indexer's scores, `latent.select_top` picks
    each query's `index_topk` positions among those it may see, and
    `ops.paged_attention.latent_attention` walks the live pages of `kv`
    under that selection. What is per (query, key) is sized by a BUCKET of
    the table (`ladder`), chosen by `lax.switch` from the farthest position
    any active slot's query reaches, and the kernels' walks by each slot's
    own live pages: a slot that holds 40 tokens, or none, pays for neither
    the table nor its neighbours. While no query sees more than
    `index_topk` positions the indexer's scores are not computed at all
    (every position is selected); its keys are written all the same.

    The layers run UNROLLED (`block_layers(params["blocks"])`), the pool
    threaded through them flat, each layer's weights read where they lie:
    a scan over a stack of expert layers, 1.2 GB, copies each layer's
    weights out of it every step (on the chip, 60% of the step; PERF.md
    section 6). The model's own `norm_eps` and `rope_base` apply. LoRA
    adapters, int8 pages and an `mp` mesh are the engine's to refuse: these
    programs take `adapters` for the signature's sake and want none."""
    from ..ops.paged_attention import (
        index_scores, latent_attention, latent_block_pages,
    )
    from . import latent as la
    from .moe import ExpertLayer

    lat, n_heads = model.latent, model.n_heads
    eps, base = model.norm_eps, model.rope_base
    ps = int(page_size)

    def whole(n_pages: int) -> int:
        """`n_pages` rounded up to whole blocks of the kernels' walk."""
        block = latent_block_pages(n_pages)
        return -(-n_pages // block) * block

    def ladder(max_pages: int) -> list:
        """Page counts of the buckets: what holds `index_topk` positions,
        then four times as much a rung, up to the table; whole blocks."""
        top = whole(max_pages)
        rungs = [min(whole(-(-lat.index_topk // ps)), top)]
        while rungs[-1] < top:
            rungs.append(min(whole(4 * rungs[-1]), top))
        return rungs

    def blocked_positions(n_pages: int):
        """[n_blocks, 1, block * page_size]: the virtual position of every
        key of a bucket, in the kernels' blocked layout."""
        t_blk = latent_block_pages(n_pages) * ps
        return jnp.arange(n_pages * ps, dtype=jnp.int32).reshape(
            -1, 1, t_blk)

    def attend(n_pages: int, select: bool):
        """One rung: attention over the first `n_pages` of every table."""
        def run(qf, qi, wi, pool, pages, live, posr):
            pages, live = pages[:, :n_pages], jnp.minimum(live, n_pages)
            seen = (blocked_positions(n_pages)[None]
                    <= posr[:, None, :, None])          # [B, NB, C, T_blk]
            if select:
                with layer_scope("index"):
                    scores = index_scores(qi, wi, pool["ik"], pages, live)
                    seen = la.select_top(scores, seen, lat.index_topk,
                                         (1, 3))
            with layer_scope("attn"):
                bias = jnp.where(seen, 0.0, la.RULED_OUT).astype(jnp.float32)
                return latent_attention(qf, pool["kv"], pages, live, bias,
                                        lat.kv_rank)
        return run

    def forward(params, adapters, cache, pages, tokens, pos0, wmask, active):
        """tokens [B, C] at positions pos0 .. pos0 + C - 1 of their slots'
        tables `pages` [B, max_pages]; `wmask` [B, C] says whose rows are
        written (the others' go to the null page), `active` [B] whose
        queries are wanted. -> (hidden [B, C, d], cache)."""
        if adapters:
            raise NotImplementedError(LATENT_ADAPTERS)
        x = dequant_leaf(params["embed"]["embedding"], dtype)[tokens]
        b_, c = tokens.shape
        pos0 = jnp.asarray(pos0, jnp.int32)
        posr = pos0[:, None] + jnp.arange(c)                  # [B, C]
        max_pages = pages.shape[1]
        rowidx = posr // ps
        wpage = jnp.where(
            wmask & (rowidx < max_pages),
            pages[jnp.arange(b_)[:, None],
                  jnp.minimum(rowidx, max_pages - 1)], 0)
        woff = posr % ps
        rungs = ladder(max_pages)
        pages = jnp.pad(pages, ((0, 0), (0, rungs[-1] - max_pages)))
        # pages that hold a position some wanted query may see, and the
        # rung that holds the farthest of them
        reach = jnp.where(active, pos0 + c, 0)
        live = -(-reach // ps)
        selecting = [r for r in rungs if r * ps > lat.index_topk]
        branches = [attend(rungs[0], False)] + [
            attend(r, True) for r in selecting]
        # rung 0 while no query sees more than `index_topk` positions, else
        # the first selecting rung that holds the farthest position
        short = jnp.sum(-(-jnp.max(reach) // ps) > jnp.asarray(
            selecting or [0], jnp.int32))
        rung = jnp.where(jnp.max(reach) <= lat.index_topk, 0,
                         1 + jnp.minimum(short, len(selecting) - 1))

        def layer(x, pool, lbase, bl):
            with layer_scope("attn"):
                h = la.rms_norm(x, dequant_leaf(bl["RMSNorm_0"]["scale"],
                                                dtype), eps)
                c_q, q_nope, q_rope, c_kv, k_rope = la.project(
                    bl, h, posr, lat, n_heads, eps, base)
                qf = la.absorb_queries(bl, q_nope, q_rope, lat)
            with layer_scope("index"):
                qi, ki, wi = la.index_inputs(bl, h, c_q, posr, lat, eps, base)
            with layer_scope("kv_write"):
                pool = {
                    "kv": pool["kv"].at[lbase + wpage, woff].set(
                        la.cached_row(c_kv, k_rope, lat)),
                    "ik": pool["ik"].at[lbase + wpage, woff].set(ki)}
            operands = (qf, qi, wi, pool, lbase + pages, live, posr)
            o_lat = (branches[0](*operands) if len(branches) == 1 else
                     jax.lax.switch(rung, branches, *operands))
            with layer_scope("attn"):
                x = x + la.expand_values(bl, o_lat, lat) @ dequant_leaf(
                    bl["wo"]["kernel"], dtype)
            if "moe" not in bl:
                with layer_scope("mlp"):
                    return swiglu_mlp(bl, None, 0.0, x, dtype, eps), pool
            with layer_scope("moe"):
                h = la.rms_norm(x, dequant_leaf(bl["RMSNorm_1"]["scale"],
                                                dtype), eps)
                return x + ExpertLayer(model.moe).apply(
                    {"params": bl["moe"]}, h), pool

        n_pool = cache["kv"].shape[1]
        pool = {name: leaf.reshape((-1,) + leaf.shape[2:])
                for name, leaf in cache.items()}
        for i, bl in enumerate(block_layers(params["blocks"])):
            x, pool = layer(x, pool, i * n_pool, bl)
        return x, {name: leaf.reshape(cache[name].shape)
                   for name, leaf in pool.items()}

    def head(params, x):
        with layer_scope("head"):
            return lm_head_logits(params, None, 0.0, x, dtype, eps)

    def chunk(params, adapters, cache, pages_row, tokens, t0, length):
        length = jnp.asarray(length, jnp.int32)
        x, cache = forward(
            params, adapters, cache, pages_row[None], tokens,
            jnp.asarray(t0, jnp.int32)[None],
            (jnp.arange(tokens.shape[1]) < length)[None],
            jnp.ones((1,), bool))
        last = jax.lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                            keepdims=False)
        return cache, head(params, last[None, None])[:, 0]

    def verify(params, adapters, cache, pages, pos, tokens, active):
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                               (tokens.shape[0],))
        x, cache = forward(
            params, adapters, cache, pages, tokens, pos,
            jnp.broadcast_to(active[:, None], tokens.shape), active)
        return cache, head(params, x)

    def step(params, adapters, cache, pages, pos, token, active):
        cache, logits = verify(params, adapters, cache, pages, pos,
                               token[:, None], active)
        return cache, logits[:, 0]

    def chunk_batch(params, adapters, cache, pages, tokens, t0, lengths):
        lengths = jnp.asarray(lengths, jnp.int32)
        x, cache = forward(
            params, adapters, cache, pages, tokens, t0,
            jnp.arange(tokens.shape[1])[None, :] < lengths[:, None],
            lengths > 0)
        last = jax.vmap(lambda xr, n: jax.lax.dynamic_index_in_dim(
            xr, jnp.maximum(n, 1) - 1, axis=0, keepdims=False))(x, lengths)
        return cache, head(params, last[:, None])[:, 0]

    return chunk, step, verify, chunk_batch


def ngram_propose(hist, pos, k: int, w: int = 2):
    """Self-drafting n-gram / prompt-lookup proposer (in-jit, the draft
    side of greedy-exact speculative decoding): for each slot, find the
    most recent PREVIOUS occurrence of the trailing `w`-gram
    `hist[pos-w+1 .. pos]` in that slot's own token history and propose
    the `k` tokens that followed it. No draft model, no extra forward —
    repetitive traffic (code, templates, retrieval echoes) is predicted
    by its own past.

    hist: [S, T] int32 token history; hist[s, :pos[s]+1] must be the
    slot's true tokens (prompt + generated) — entries PAST pos may be
    stale rejected drafts and are never trusted as match anchors, though
    a continuation may run into them (drafts are proposals; the verify
    forward decides, so a bad draft costs acceptance, never correctness).
    pos: [S] position of the last known token. Returns [S, k] drafts;
    slots with no match fall back to repeating their last token (the
    self-loop draft — exactly right for the degenerate repetition case).
    """
    s_, t = hist.shape
    idx = jnp.arange(t)[None, :]                          # [1, T]
    # candidate continuation start j: positions j-w..j-1 hold the same
    # w-gram as positions pos-w+1..pos; j must be a PAST point (<= pos)
    # with a full gram before it (>= w)
    match = (idx >= w) & (idx <= pos[:, None])
    for shift in range(w):
        a = jnp.take_along_axis(
            hist, jnp.maximum(idx - 1 - shift, 0), axis=1)     # [S, T]
        b = jnp.take_along_axis(
            hist, jnp.maximum(pos[:, None] - shift, 0), axis=1)  # [S, 1]
        match = match & (a == b)
    found = jnp.any(match, axis=1)
    # most recent occurrence wins (largest j): recency beats frequency
    # for the loops/templates this draft exists to predict
    j = jnp.max(jnp.where(match, idx, 0), axis=1)         # [S]
    gidx = jnp.minimum(j[:, None] + jnp.arange(k), t - 1)
    draft = jnp.take_along_axis(hist, gidx, axis=1)       # [S, k]
    last = jnp.take_along_axis(hist, pos[:, None], axis=1)
    return jnp.where(found[:, None], draft, last)


def make_generate(n_heads: int, alpha: float = 16.0,
                  dtype=jnp.float32, eps: float = 1e-6,
                  sample: bool = False, top_k: int = 0,
                  prefill_attn_fn=None):
    """generate(params, adapters, tokens, max_len, n_steps, length=None,
    rng=None, temperature=1.0) -> [n_steps] tokens for batch-1 prompts —
    prefill once, then a lax.scan of KV-cached steps, all inside the
    caller's jit (n_steps/max_len static).

    sample=False (default) is greedy argmax. sample=True draws from
    softmax(logits / temperature) with an optional static top_k cutoff
    (the HF generate() sampling knobs the reference's serving inherits);
    temperature is TRACED, so one compiled program covers every
    temperature, while top_k and sample are compile-time. `rng` may be a
    single key (one stream shared by the batch) or a [B] key array —
    per-row streams, under which batched row i samples the exact tokens
    decoding prompt i alone with rng[i] would."""
    prefill, step = make_kv_decode(n_heads, alpha=alpha, dtype=dtype,
                                   eps=eps, prefill_attn_fn=prefill_attn_fn)

    def pick(logits, key, temperature):
        if not sample:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        l = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
        if top_k:
            kth = jax.lax.top_k(l, top_k)[0][..., -1:]
            l = jnp.where(l < kth, -jnp.inf, l)
        if _batched_keys(key):
            # PER-ROW keys ([B] key array): each batched row draws with its
            # own stream, so row i reproduces exactly what decoding that
            # prompt ALONE with keys[i] would draw (a shared key would give
            # the batch one [B, V] gumbel field whose row i differs from
            # the batch-1 field — batched/solo sampling parity needs this)
            return jax.vmap(
                lambda k, row: jax.random.categorical(k, row, -1))(
                    key, l).astype(jnp.int32)
        return jax.random.categorical(key, l, -1).astype(jnp.int32)

    def generate(params, adapters, tokens, max_len: int, n_steps: int,
                 length=None, rng=None, temperature=1.0):
        """tokens may be right-padded to a bucket with `length` the real
        prompt length(s) (traced ok; scalar or per-row [B]) — the
        predictor uses this so compiled programs are keyed by (prompt
        bucket, step bucket), not by every distinct prompt length.

        Returns [n_steps] tokens for batch-1 prompts, [B, n_steps] for a
        batch (rows may have different real lengths; every row decodes
        n_steps tokens in lockstep through one program)."""
        if rng is None:
            rng = jax.random.key(0)

        def fold(key, i):
            # rng may be one key (shared stream, the serving default —
            # typed or legacy uint32[2]) or a [B] typed key array
            # (per-row streams — see pick())
            if _batched_keys(key):
                return jax.vmap(jax.random.fold_in,
                                in_axes=(0, None))(key, i)
            return jax.random.fold_in(key, i)

        cache, logits = prefill(params, adapters, tokens, max_len,
                                length=length)
        first = pick(logits, fold(rng, 0), temperature)
        b = tokens.shape[0]
        pos0 = jnp.broadcast_to(
            jnp.asarray(tokens.shape[1] if length is None else length,
                        jnp.int32), (b,))

        def one(carry, i):
            cache, tok = carry
            cache, logits = step(params, adapters, cache, pos0 + i, tok)
            nxt = pick(logits, fold(rng, i + 1), temperature)
            return (cache, nxt), nxt

        # n_steps - 1 decode steps: token 1 comes from prefill, and the
        # last emitted token needs no further step (scanning n_steps would
        # pay one full per-layer pass whose result is discarded)
        (_cache, _tok), rest = jax.lax.scan(
            one, (cache, first), jnp.arange(n_steps - 1))
        toks = jnp.concatenate([first[None], rest], axis=0)  # [n_steps, B]
        return toks[:, 0] if b == 1 else toks.T

    return generate


def make_greedy_generate(n_heads: int, alpha: float = 16.0,
                         dtype=jnp.float32, eps: float = 1e-6):
    """Greedy specialization of make_generate (kept as the stable name the
    predictor and tests use)."""
    return make_generate(n_heads, alpha=alpha, dtype=dtype, eps=eps,
                         sample=False)
