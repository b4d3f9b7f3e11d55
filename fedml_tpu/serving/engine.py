"""Continuous-batching decode engine: slot-based LLM serving on one
persistent, donated, PAGED KV pool.

Why: the per-request serving path (serving/predictor.py GreedyLMPredictor)
runs each request's prefill+decode as its own device program end-to-end, so
N concurrent users get N serialized programs — aggregate tokens/sec is flat
in concurrency while the chip idles between requests. This module is the
vLLM-style iteration-level scheduler over llm/decode.py's paged programs
(`make_paged_kv_decode`): per-slot positions, block-allocated KV storage.

Shape of the thing:

- The engine owns S decode *slots* backed by ONE persistent KV POOL
  (`{"k","v"}: [L, kv_n_pages, page_size, H, Dh]`) plus an int32
  `[S, max_pages]` page table INSIDE the donated carry: it stays
  device-resident across requests, every jitted call DONATES the carry so
  XLA updates it in place, and a program moves the rows it touches, never
  the pool. Persistent HBM is `kv_n_pages x page_size` token rows; page 0
  is the reserved null page that absorbs inactive/padded writes. The
  default pool (`n_pages=None`) holds every slot at `max_len`; pass fewer
  pages to size it to LIVE tokens. A model of latent-attention layers
  (llm/latent.py) has the pool's other layout, which has NO heads axis:
  `{"kv": [L, kv_n_pages, page_size, width], "ik": [..., index_dim]}`, one
  compressed row and one indexer key a token, and
  `make_paged_latent_decode`'s programs in `make_paged_kv_decode`'s place
  (expert layers in the step; int8 pages, `mp > 1` and LoRA adapters
  refused for it by name). Everything below (pages, the table, chunked
  prefill, the prefix cache, retirement) is the same for both. The pool of
  per-head rows holds as many KV heads as the model has (grouped heads
  share them), heads of the model's own `head_dim`.
- Admission allocates a request's pages (ceil((prompt+max_new)/page_size),
  reserved up front so a mid-decode slot can never hit page exhaustion)
  from a host free list; retirement returns them. The free list + prefix
  map are host state — the page TABLE is the device-side structure the
  programs consume; allocation is a host decision because prefix sharing
  keys on token content the device never sees.
- CHUNKED PREFILL: admission writes the prompt in `prefill_chunk`-sized
  pieces (0 = the whole prompt in one chunk), ONE chunk per engine
  iteration, round-robin across in-flight admissions — decode slots
  advance between chunks, so a long prompt does not stall all S slots for
  its full prefill, and a short prompt admitted alongside a long one
  reaches its first token in time proportional to its OWN length. Chunks
  are right-padded to a power-of-two bucket (real length traced; the same
  bucketing contract as the per-request path); the FINAL chunk's
  last-position logits yield the request's first token inside the same
  program.
- PREFIX CACHE: full pages of a prompt are registered in a content-hash
  chain map (hash over token IDS per page, chained — resident pages are
  ref-counted; refs==0 entries stay resident and evict LRU, leaf-first,
  only under allocation pressure). A request whose prompt prefix is
  already resident starts its chunked prefill AFTER the hit (capped at
  prompt_len - 1 so the first-token logits are always computed), so
  identical system prompts — the dominant traffic shape — stop
  recomputing K/V and their TTFT goes ~flat in prompt length.
- Every engine iteration advances ALL slots one token through a single
  jitted step with per-slot `pos`, per-slot traced temperature + rng seed,
  and an active-mask so idle slots are inert (their K/V writes are
  redirected to the null page).
- Retirement is decided ON DEVICE: a slot deactivates when it hits its
  per-request token budget (`limit`) or emits `eos_id`; the host merely
  observes the mask in fetched frames, completes the ticket, and returns
  the slot and its pages to the free lists.
- The host loop dispatches ahead: step/admit outputs queue as device
  arrays and are fetched in small chunks (`fetch_chunk`), so admission and
  retirement bookkeeping overlap device execution — no per-step
  `device_get` barrier.

Compiled-program set stays BOUNDED: one step program (all S slots, every
temperature/seed traced) + one chunk program per chunk bucket
(log2(prefill_chunk) + 1 of them at most). `program_counts()` exposes the
live jit cache sizes; tests pin them.

DECODE RAW SPEED (ISSUE 11) — two legs, both token-identity pinned
(tests/test_decode_kernel_spec.py):

- `paged_kernel=True` swaps the step's gather-then-attend for the fused
  Pallas paged-attention kernel (ops/paged_attention.py): pages are read
  IN PLACE through the device-side page table, only those that hold a
  position a live slot attends; the virtually-contiguous copy never
  materializes. The gather path stays as the test oracle; CPU runs the
  same kernel under interpret mode, so tier-1 exercises the real kernel
  body.
- `spec_decode="ngram"` attacks per-token latency itself: each
  iteration self-drafts `spec_k` tokens from the slot's OWN history
  (prompt-lookup n-gram — no second model), verifies the whole window
  in ONE batched target forward over the paged cache, and accepts the
  longest prefix the target itself would have produced. Greedy-exact by
  construction (a token is only accepted when every input before it was
  the target's own pick), and the same argument covers seeded sampling
  because the per-position rng schedule is the plain step's. Rollback
  is positional: pos advances only past accepted tokens, so the next
  window re-writes rejected positions' pages before anything reads
  them. Accept telemetry: `serving.spec.proposed` / `.accepted`
  counters, accept-rate on the `top` engine line.

GENERATION BY DIFFUSION OVER BLOCKS (a model with `diffusion_block` = B > 0,
llm/transformer.py): the iteration is neither the step nor the verify window
but `_block_all`: every live slot advances its current BLOCK by one forward
over the block's B positions (`<|MASK|>` where still masked), and yields 0
to B tokens. The carry holds, per slot, the block's token ids, which are
still masked, the forward's index within the block and the request's
`denoising_steps` and `confidence_threshold` as traced values. On device:
every masked position's pick and its probability (the confidence); the
unmask rule (the `ceil(B / steps)` most confident, and every one over the
threshold; an unmasked token is final); when a block enters a forward with
nothing masked that forward is the COMMIT (its K/V writes are the block's
final ones) and the next block starts; retirement when the run of final
tokens from the block's start reaches the budget or an `eos`. Admission
prefills the prompt's whole blocks, yields NO token, and seeds the first
block with the prompt's tail: the first token comes from a denoising
forward. The host streams, after each forward, the longest run of final
tokens from the block's start, each with the forward index at which it was
unmasked and its confidence (`Ticket.note`). Pages are a whole number of
blocks, so a prompt's full pages are shared as any other model's. Counters:
`serving.engine.block_forwards` / `.commit_forwards` (live slots a drained
block frame, and those of them that were commits), `.block_positions` (live
slots x B), `.unmasked_tokens`, `.block_context` (the positions a live
slot's window attends, once a forward: what the paged kernel has to read),
and from what the expert layers sow `.moe_pairs` and `.moe_experts_live`; `steps`, `slot_steps`, `page_steps`
and `context_keys` count block forwards as they count steps.

Capacity contract per slot: `prompt_len + max_new_tokens <= max_len`
(no step bucketing — the engine emits exactly the tokens asked for, so
unlike the per-request path max_new_tokens is not rounded up) AND the
page-budget term: ceil((prompt + max_new) / page_size) must fit the
usable pool (kv_n_pages - 1 — page 0 is reserved);
`admissible()` is the one capacity oracle the predictor's routing and
degrade refusal consult, and the submit error message states the page
math.

Equivalence contract: for identical prompts, greedy engine output is
token-identical to the per-request path — the slot axis is data-parallel
through the decode math, and the page table only renames where a
position's K/V rows live (pinned in tests/test_serving_engine.py and
tests/test_paged_engine.py).

FLEET ROBUSTNESS (ISSUE 9) — the three production failure shapes a
federated deployment meets are model churn, overload, and mid-request
replica death; the engine carries the first and last:

- HOT ADAPTER SWAP: `swap_adapters(tree, version=)` replaces the LoRA
  adapter values ATOMICALLY between decode iterations — no KV-cache
  teardown, no restart, no recompile. The compiled step/admit programs
  are layout-stable because adapters are replicated (the
  `partition.TABLES["lora"]` contract), so only the VALUES may change:
  a swap whose tree structure/shapes/dtypes differ from the serving
  tree is refused (that change needs a redeploy, and silently accepting
  it would retrace every program). In-flight requests finish on the NEW
  adapters from their next step — the federated rolling-update
  semantic: round N+1's adapters take effect mid-decode rather than
  holding traffic. `model_version` is monotonic and rides the
  `serving.model_version` gauge + a `serving.swap` span.
- STREAMING TICKETS: `Ticket.stream()` yields tokens AS the host
  observes their retirement frames (granularity = `fetch_chunk`), so
  the HTTP tier can emit SSE chunks while the request still decodes;
  `result()` is unchanged.
- GRACEFUL DRAIN: `stop(drain=True)` refuses new submits and lets every
  accepted request finish (bounded by `drain_timeout_s`) before
  teardown — a scale-down or rolling replica replacement never errors a
  ticket that was already decoding.

Telemetry rides the existing planes: `serving.ttft` / `serving.tbt`
histograms, `serving.slots_active` gauge, `serving.tokens_total` counter,
`serving.engine.*` counters (`steps` / `slot_steps`: drained step frames
and the live slots in them, whose ratio is the slot occupancy;
`page_steps`: the pages those slots' queries attended, which over `steps` x
the `serving.engine.table_pages` gauge is the share of the page table a
step has to walk; `context_keys` / `selected_keys`: the keys the queries of
drained steps and dispatched prefill chunks see and those their softmax
weighs, fewer only where an indexer selects: arithmetic on positions, not
what the kernel read), the
`serving.prompt_tokens` / `serving.prefix_hit_tokens` counters of admission,
and `serving.engine.admit` / `.fetch` spans on the Chrome trace — all visible
in `/metrics` and `python -m fedml_tpu top`. Each request also leaves three
contiguous spans in its caller's trace once its consumer has the first
token (`Ticket._record_spans`): `serving.engine.queue`, `.prefill`,
`.first_fetch`; the device programs carry `decode.*` named scopes
(llm/decode.py `layer_scope`).
"""
from __future__ import annotations

import hashlib
import heapq
import logging
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import enable_compilation_cache
from ..utils import metrics as _mx
from ..utils import xla_ledger as _ledger
from ..utils.events import current_trace, recorder
from .predictor import InvalidRequest, _bucket

log = logging.getLogger(__name__)
Pytree = Any
# a block-diffusion request's confidence threshold where it names none (the
# SDAR family's published default; a request's null means the static rule)
CONFIDENCE_THRESHOLD = 0.9


def _page_key(parent: bytes, tokens) -> bytes:
    """Chain hash for one prefix page: keyed on the page's TOKEN IDS (an
    int32 byte view — [12, 3] and [1, 23] must not collide the way naive
    string concatenation would) chained through the parent page's key, so
    a key identifies the FULL token prefix up to and including this page."""
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


class _PrefixEntry:
    """One resident prefix page: refs counts live users (slots decoding
    over it); kids counts resident chain extensions. Evictable only at
    refs == 0 AND kids == 0 (evicting a mid-chain page would strand its
    extensions — resident but unreachable by the incremental hash walk)."""

    __slots__ = ("page", "parent", "refs", "kids", "tick")

    def __init__(self, page: int, parent: Optional[bytes], tick: int):
        self.page = page
        self.parent = parent
        self.refs = 1
        self.kids = 0
        self.tick = tick


class _Admission:
    """One in-flight chunked admission: `row` is the slot's full page-table
    row (prefix-hit pages + freshly allocated ones), `t0` the next prompt
    position to prefill (starts at the page-aligned hit length), `keys`
    the chain hashes for every FULL prompt page (computed once at lookup,
    reused at registration)."""

    __slots__ = ("req", "slot", "row", "t0", "keys", "hit_pages", "total")

    def __init__(self, req, slot, row, t0, keys, hit_pages, total):
        self.req = req
        self.slot = slot
        self.row = row
        self.t0 = t0
        self.keys = keys
        self.hit_pages = hit_pages
        self.total = total


class Ticket:
    """Per-request handle: the HTTP handler blocks on `result()` while the
    engine thread decodes — requests no longer serialize through one
    global jit call; concurrency is bounded by slots, not threads.

    Tokens are PUSHED as the host observes their retirement frames, so
    `stream()` can relay them while the request still decodes (the SSE
    serving surface); `result()` keeps the block-until-done contract."""

    __slots__ = ("_cv", "_done", "_tokens", "_notes", "_error", "trace",
                 "t_submit", "t_slot", "t_prefilled", "t_first", "t_done",
                 "prefill", "_spanned")

    def __init__(self, prompt: int = 0):
        self._cv = threading.Condition()
        self._done = threading.Event()
        self._tokens: list[int] = []
        # beside each token of a block-diffusion model: (the forward index
        # within its block at which it was unmasked, its confidence)
        self._notes: list[tuple] = []
        self._error: Optional[BaseException] = None
        # created by submit() on the caller's thread (the HTTP handler's,
        # inside its `serving.request` span): the spans of this request's
        # life inside the engine join that trace under that span
        self.trace = current_trace()
        self.t_submit = time.perf_counter()
        self.t_slot: Optional[float] = None        # a slot was claimed
        self.t_prefilled: Optional[float] = None   # final chunk dispatched
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        # what its prefill took: chunks dispatched, prompt pages the prefix
        # cache already held (the engine counts, the spans carry them)
        self.prefill = {"chunks": 0, "prompt": prompt, "hit_pages": 0}
        self._spanned = False

    def _record_spans(self) -> None:
        """The request's life inside the engine up to its first token, as
        three contiguous spans of its trace: `queue` (submit to the slot
        claimed), `prefill` (to the final chunk DISPATCHED) and
        `first_fetch` (to the token pushed: the chunk's own device time
        plus the frames in flight ahead of it); they add up to `t_first -
        t_submit`. The engine thread only stamps; the CONSUMER's thread
        records, once, after it has its first token (`stream`) or its
        result: the loop that paces every request stays as lean as it was."""
        if self._spanned or None in (self.t_slot, self.t_prefilled,
                                     self.t_first):
            return
        self._spanned = True
        trace_id, parent = self.trace
        s = recorder.record_span(
            "serving.engine.queue", self.t_submit, self.t_slot,
            trace_id=trace_id, parent_id=parent)
        # a ticket submitted outside any span starts a trace of its own
        recorder.record_span(
            "serving.engine.prefill", self.t_slot, self.t_prefilled,
            trace_id=s.trace_id, parent_id=parent, **self.prefill)
        recorder.record_span(
            "serving.engine.first_fetch", self.t_prefilled, self.t_first,
            trace_id=s.trace_id, parent_id=parent)

    # engine-thread side -------------------------------------------------
    def _push(self, tok: int, note: Optional[tuple] = None) -> None:
        with self._cv:
            self._tokens.append(tok)
            if note is not None:
                self._notes.append(note)
            self._cv.notify_all()

    def note(self, i: int) -> Optional[tuple]:
        """(forward index, confidence) of token `i` of a block-diffusion
        model's answer; None for a model that emits a token a step."""
        with self._cv:
            return self._notes[i] if i < len(self._notes) else None

    def _finish(self, error: Optional[BaseException] = None) -> None:
        with self._cv:
            if error is not None and self._error is None:
                self._error = error
            self._done.set()
            self._cv.notify_all()

    # caller side --------------------------------------------------------
    def result(self, timeout: Optional[float] = None) -> list[int]:
        """Block until the request retires; returns the generated tokens
        (the eos token, when one ended generation, is included)."""
        if not self._done.wait(timeout):
            raise TimeoutError("decode engine ticket not done "
                               f"after {timeout}s")
        self._record_spans()
        if self._error is not None:
            raise self._error
        with self._cv:
            return list(self._tokens)

    def stream(self, timeout: Optional[float] = None):
        """Yield tokens as the engine retires them (granularity = the
        engine's `fetch_chunk` frames). `timeout` bounds the wait for
        EACH next token, not the whole request. Raises the ticket's
        error (engine crash / stop) after yielding whatever tokens
        arrived before it — the caller decides how a half-stream is
        surfaced."""
        i = 0
        while True:
            with self._cv:
                while i >= len(self._tokens) and not self._done.is_set():
                    if not self._cv.wait(timeout):
                        raise TimeoutError(
                            f"no token from the decode engine in {timeout}s")
                if i >= len(self._tokens):
                    if self._error is not None:
                        raise self._error
                    return
                tok = self._tokens[i]
            yield tok           # outside the lock: the consumer may block
            i += 1
            self._record_spans()    # the consumer has its first token

    def done(self) -> bool:
        return self._done.is_set()


# the ticket the calling thread's last submit() returned. The HTTP handler
# never sees a streamed request's ticket (the predictor's generator owns it)
# and needs its stamps to time its own two ends of the time to first token.
_submitted = threading.local()


def submitted_ticket(trace_id: Optional[str]) -> Optional[Ticket]:
    """The ticket this thread submitted inside trace `trace_id`, if any (a
    request served by the per-request fallback submitted none)."""
    tk = getattr(_submitted, "ticket", None)
    if tk is None or not trace_id or tk.trace[0] != trace_id:
        return None
    return tk


class _Request:
    __slots__ = ("tokens", "max_new", "temperature", "seed", "ticket",
                 "steps", "threshold")

    def __init__(self, tokens, max_new, temperature, seed, steps=0,
                 threshold=None):
        self.tokens = tokens
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed
        # a block-diffusion request's denoising forwards a block and its
        # confidence threshold (None: the static rule)
        self.steps = steps
        self.threshold = threshold
        self.ticket = Ticket(len(tokens))


class _Swap:
    """One queued hot adapter swap, applied by the engine thread between
    decode iterations; `applied` releases the waiting caller."""

    __slots__ = ("adapters", "version", "applied", "error")

    def __init__(self, adapters, version: int):
        self.adapters = adapters
        self.version = version
        self.applied = threading.Event()
        self.error: Optional[BaseException] = None


def check_adapter_swap(current: Pytree, new: Pytree) -> None:
    """The layout-stability contract behind hot swap: the replacement
    adapter tree must match the serving tree's STRUCTURE, shapes, and
    dtypes exactly — those are baked into every compiled program (and,
    on a mesh, into the pinned shardings), so a mismatch would force a
    retrace (or worse, silently serve garbage). Raises ValueError naming
    the first offending leaf."""
    cur_flat = jax.tree_util.tree_flatten_with_path(current)[0]
    new_flat = jax.tree_util.tree_flatten_with_path(new)[0]
    cur_td = jax.tree_util.tree_structure(current)
    new_td = jax.tree_util.tree_structure(new)
    if cur_td != new_td:
        raise ValueError(
            "adapter swap tree structure differs from the serving tree — "
            "a structural change retraces every compiled program; "
            "redeploy the replica instead (hot swap replaces VALUES of "
            "the layout the engine was built with)")
    for (path, a), (_p, b) in zip(cur_flat, new_flat):
        if a.shape != b.shape or a.dtype != b.dtype:
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            raise ValueError(
                f"adapter swap leaf {name!r} is {b.shape}/{b.dtype}; the "
                f"serving tree has {a.shape}/{a.dtype} — shapes and dtypes "
                "are compile-time constants of the decode programs")


def prepare_adapter_swap(current: Pytree, adapters: Pytree, n_layers: int,
                         current_version: int, version: Optional[int],
                         who: str = "the engine") -> tuple[Pytree, int]:
    """The validate-and-version step shared by DecodeEngine.swap_adapters
    and GreedyLMPredictor's no-engine fallback: stack the per-block
    adapter tree, refuse empty trees and layout changes
    (check_adapter_swap), and compute the monotonic target version.
    Returns (stacked_tree, new_version)."""
    from ..llm.decode import stack_adapter_blocks

    stacked = stack_adapter_blocks(adapters, n_layers)
    if not stacked:
        raise ValueError("swap_adapters needs a non-empty adapter tree")
    check_adapter_swap(current, stacked)
    ver = current_version + 1 if version is None else int(version)
    if ver <= current_version:
        raise ValueError(
            f"model_version must be monotonic: swap to {ver} but "
            f"{who} already serves {current_version}")
    return stacked, ver


class _SlotState:
    """Host-side view of an occupied slot (the device mask is the source
    of truth for retirement; this mirrors it frame-by-frame), with what
    retirement must release: `entries` (prefix
    pages this slot holds a ref on) and `private` (pages owned outright —
    the prompt tail, the decode budget, and any page whose registration
    lost a race to a concurrent identical prompt)."""

    __slots__ = ("req", "out", "t_first", "entries", "private",
                 "block_pos", "run", "notes")

    def __init__(self, req: _Request, block: int = 0):
        self.req = req
        req.ticket.t_slot = time.perf_counter()   # the queue wait ends here
        self.out: list[int] = []
        self.t_first: Optional[float] = None
        self.entries: list[_PrefixEntry] = []
        self.private: list[int] = []
        # a block-diffusion slot: where its current block starts, how many
        # of the block's positions the streamed run has passed, and each
        # unmasked position's (forward index, confidence)
        self.block_pos = len(req.tokens) // block * block if block else 0
        self.run = 0
        self.notes: dict = {}


class DecodeEngine:
    """S-slot continuous-batching decoder over llm/decode.py's functional
    prefill/step.

    `model` is a llm.TransformerLM (its n_layers/n_heads/d_model size the
    cache); `params`/`adapters` may be unrolled or scan-layout (stacked
    here, pass-through if already stacked) and float or int8 {q,s}.
    `eos_id=None` disables eos retirement (requests always run their full
    max_new_tokens — the mode the greedy-equivalence contract is pinned
    in). Sampling: per-slot traced temperature; temperature <= 0 means
    greedy; full-vocab categorical (top_k requests stay on the
    per-request path, which compiles a static-k cutoff).

    `mesh` (a jax Mesh with an `mp` axis) runs the engine TENSOR-PARALLEL:
    weights and the persistent KV pool shard over `mp` via the
    parallel/partition.py rule registry (`partition_rules` overrides the
    default `transformer_lm` table) — the scale-out path for models whose
    KV pool + weights exceed one chip's HBM (pages replicate; the pool
    shards its heads axis). Greedy output is token-identical across mp
    sizes (pinned at mp=1 vs mp=2 in tests).

    `page_size` (>= 1) is the KV page (module docstring): `n_pages` sizes
    the pool (default = every slot at max_len + the null page; pass less
    to trade peak concurrency for HBM), `prefill_chunk` bounds how many
    prompt tokens one admission program processes (0 = whole prompt in
    one chunk), `prefix_cache` toggles content-hash prefix page reuse.

    `paged_kernel=True` runs decode attention through the fused Pallas
    kernel (ops/paged_attention.py — pages read in place, no gather
    copy); `spec_decode="ngram"` + `spec_k` turns each iteration into a
    self-drafted speculative verify window that emits up to spec_k + 1
    tokens, greedy-exact (module docstring). Both compose with each
    other and with `mesh`. (Latent pages have ONE attention path, the
    kernels': `paged_kernel` chooses nothing for such a model.)

    `kv_quant="int8"` stores the persistent pool in int8 with
    per-(page, head) scales riding the carry — half the KV HBM per slot,
    so ~2x decode slots at a fixed pool budget, for a <1pt greedy
    match-rate delta (quantize-at-write / dequantize-at-gather; the
    Pallas kernel dequants each slab in VMEM). `admit_batch` > 1 admits
    up to that many same-bucket pending prompts per engine iteration
    through ONE batched chunk program — burst TTFT p99 stops paying one
    dispatch per request. Both compose with each other, the kernel, spec
    decode, and `mesh`.

    A model with `diffusion_block` > 0 generates a block at a time (module
    docstring); `spec_decode`, `kv_quant='int8'`, `admit_batch` > 1 and a
    mesh are refused for it by name, and `page_size`, `prefill_chunk` and
    `max_len` must be whole numbers of blocks."""

    def __init__(self, model, params: Pytree,
                 adapters: Optional[Pytree] = None, *,
                 n_slots: int = 4, max_len: int = 256,
                 eos_id: Optional[int] = None,
                 dtype=None, fetch_chunk: int = 2,
                 mesh=None, partition_rules=None,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefill_chunk: int = 0, prefix_cache: bool = True,
                 paged_kernel: bool = False, spec_decode: str = "off",
                 spec_k: int = 4, kv_quant: str = "off",
                 admit_batch: int = 1):
        from ..llm.decode import (
            LATENT_ADAPTERS, layer_scope, make_paged_kv_decode,
            make_paged_latent_decode,
            ngram_propose, require_servable, stack_adapter_blocks,
            stack_blocks,
        )

        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1; got {n_slots}")
        enable_compilation_cache()   # before the first trace
        require_servable(model)
        self.model = model
        self.max_len = int(max_len)
        self.n_slots = int(n_slots)
        self.fetch_chunk = max(1, int(fetch_chunk))
        # ------------------------------------------------------ page pool
        if int(page_size) < 1:
            raise ValueError(
                f"page_size must be >= 1 KV rows a page; got {page_size}")
        self._page_size = int(page_size)
        self._max_pages = -(-self.max_len // self._page_size)
        # default pool = every slot at max_len + the reserved null page;
        # the memory win comes from passing a SMALLER kv_n_pages
        self._n_pages = (int(n_pages) if n_pages
                         else self.n_slots * self._max_pages + 1)
        self._usable = self._n_pages - 1   # page 0 is the null page
        if self._n_pages < 2:
            raise ValueError(
                f"kv_n_pages must be >= 2 (page 0 is the reserved "
                f"null page); got {self._n_pages}")
        if int(prefill_chunk) < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = whole-prompt "
                f"chunks); got {prefill_chunk}")
        self._prefill_chunk = int(prefill_chunk)
        self._prefix_on = bool(prefix_cache)
        self._free_pages: list[int] = list(range(1, self._n_pages))
        self._prefix: dict[bytes, _PrefixEntry] = {}
        self._ticks = 0
        _mx.set_gauge("serving.kv_pages_budget", self._usable)
        _mx.set_gauge("serving.engine.table_pages",
                      self.n_slots * self._max_pages)
        _mx.set_gauge("serving.kv_pages_free", len(self._free_pages))
        # ------------------------------------------- decode-speed knobs
        self._kernel_on = bool(paged_kernel)
        if spec_decode not in ("off", "ngram"):
            raise ValueError(
                f"spec_decode must be 'off' or 'ngram'; got {spec_decode!r}")
        self._spec_on = spec_decode == "ngram"
        self._spec_k = int(spec_k)
        if self._spec_on and self._spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1 draft tokens; got {spec_k}")
        if kv_quant not in ("off", "int8"):
            raise ValueError(
                f"kv_quant must be 'off' or 'int8'; got {kv_quant!r}")
        self._quant = kv_quant == "int8"
        # latent pages (llm/latent.py): one row a token and no heads axis
        latent = getattr(model, "latent", None)
        # keys a query attends at most (None: every key it sees)
        self._topk = latent.index_topk if latent else None
        if latent and self._quant:
            raise NotImplementedError(
                "kv_quant='int8' on latent pages: the int8 pool keeps a "
                "scale per (page, head) (llm/decode.py _kv_quant_write) and "
                "a latent row has no heads")
        if latent and adapters:
            raise NotImplementedError(LATENT_ADAPTERS)
        self._admit_batch = int(admit_batch)
        if self._admit_batch < 1:
            raise ValueError(
                f"admit_batch must be >= 1; got {admit_batch}")
        # generation by diffusion over blocks of B positions (0: a token a
        # step). A page, a prefill chunk and a slot's table are whole
        # numbers of blocks: a prefix page is then shared only where whole
        # blocks are (16 = 4 x 4), and a chunk's mask never cuts a block
        self._block = B = int(getattr(model, "diffusion_block", 0) or 0)
        if B:
            for knob, on in (
                    ("spec_decode", self._spec_on),
                    ("kv_quant='int8'", self._quant),
                    ("admit_batch > 1", self._admit_batch > 1),
                    ("a mesh (mp > 1)", mesh is not None)):
                if on:
                    raise NotImplementedError(
                        f"{knob} with a diffusion model: a block's forward "
                        "is `_block_all` over the plain pool on one chip "
                        "(no drafted window, no per-page scales, one "
                        "request an admission program, no heads split)")
            for knob, v in (("kv_page_size", self._page_size),
                            ("prefill_chunk", self._prefill_chunk),
                            ("engine_max_len", self.max_len)):
                if v % B:
                    raise ValueError(
                        f"{knob} {v} is not a whole number of the model's "
                        f"diffusion blocks of {B}: pages, prefill chunks "
                        "and a slot's table hold whole blocks")
        self._admissions: deque[_Admission] = deque()
        # -1 never matches a token id, so eos retirement is inert
        self._eos = -1 if eos_id is None else int(eos_id)
        self.adapters = stack_adapter_blocks(adapters, model.n_layers)
        self.params = stack_blocks(params, model.n_layers)
        if dtype is not None:
            kv_dtype = jnp.dtype(dtype)
        else:
            floats = [l for l in jax.tree.leaves(self.params)
                      if jnp.issubdtype(l.dtype, jnp.floating)]
            kv_dtype = floats[0].dtype if floats else jnp.float32
        self._kv_dtype = kv_dtype

        # ------------------------------------------ tensor-parallel layout
        # `mesh` with an `mp` axis runs the engine tensor-parallel: weights
        # take the Megatron column/row layout from the ONE partition-rule
        # registry (parallel/partition.py — the SAME table the round
        # programs and CentralizedTrainer resolve, so train and serve
        # layouts cannot drift), adapters replicate (they are the round
        # payload), and the persistent KV pool [L, P, page, H, Dh] shards
        # its HEADS axis (partition.paged_kv_cache_spec) — the decode-side
        # continuation of the column-split attention projections. GSPMD
        # inserts the one all-reduce per block at the wo row matmul; with
        # mp=1 the placement is a no-op and the engine stays token-
        # identical to the unmeshed path (pinned in tests).
        self.mesh = mesh
        self.param_specs = None
        self.kv_spec = None
        kv_sharding = rep_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from ..parallel import partition

            if "mp" not in mesh.axis_names:
                raise ValueError(
                    f"DecodeEngine mesh axes {mesh.axis_names} have no "
                    "'mp' axis (the tensor-parallel axis the rule tables "
                    "shard over)")
            mp = mesh.shape["mp"]
            if latent:
                partition.paged_latent_cache_spec("mp", mp)   # refuses mp > 1
            if (model.n_kv_heads or model.n_heads) % mp:
                raise ValueError(
                    f"the {model.n_kv_heads or model.n_heads} KV heads are "
                    f"not divisible by mp={mp} — the KV pool shards the "
                    "heads axis")
            if mp > 1 and any(f == "moe" for _, f in model.kinds):
                raise NotImplementedError(
                    "expert layers under an mp mesh: the partition rules "
                    "(parallel/partition.py) split no expert's weights")
            rules = (partition_rules
                     if partition_rules is not None
                     else partition.transformer_lm_rules("mp"))
            self.param_specs = partition.match_partition_rules(
                rules, self.params)
            self.params = partition.shard_params(
                self.params, mesh, specs=self.param_specs)
            if self.adapters is not None:
                self.adapters = partition.shard_params(
                    self.adapters, mesh, "lora")
            self.kv_spec = partition.paged_kv_cache_spec("mp")
            kv_sharding = NamedSharding(mesh, self.kv_spec)
            rep_sharding = NamedSharding(
                mesh, jax.sharding.PartitionSpec())

        if latent:
            (chunk_fn, paged_step, paged_verify,
             chunk_batch_fn) = make_paged_latent_decode(
                model, self._page_size, dtype=kv_dtype)
        else:
            (chunk_fn, paged_step, paged_verify,
             chunk_batch_fn) = make_paged_kv_decode(
                model.n_heads, self._page_size, dtype=kv_dtype,
                eps=model.norm_eps, kernel=self._kernel_on, mesh=mesh,
                quant=self._quant, rope_base=model.rope_base,
                head_dim=model.head_dim, qk_norm=model.qk_norm,
                moe=model.moe if model.has_counters else None, block=B)
        S, eos, max_len_ = self.n_slots, self._eos, self.max_len

        def pick(logits, temp, key):
            """Greedy/sampled select with temperature TRACED (one program
            covers both): softmax sampling computes alongside and a where
            picks — the greedy lane is bit-identical to the per-request
            path's argmax."""
            with layer_scope("sample"):
                greedy = jnp.argmax(logits, -1).astype(jnp.int32)
                l = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[
                    ..., None]
                if logits.ndim == 1:
                    sampled = jax.random.categorical(key, l, -1)
                else:
                    sampled = jax.vmap(
                        lambda k, row: jax.random.categorical(k, row, -1))(
                            key, l)
                return jnp.where(temp > 0.0, sampled.astype(jnp.int32),
                                 greedy)

        def _admit(params, adapters, carry, tokens, t0, clen, slot,
                   row, temp, seed, limit, final, plen, *blocked):
            """ONE chunk of one request's prefill into the paged
            carry: the slot's page-table row is (re)written, the
            chunk's K/V land in its pages, and — on the FINAL chunk —
            the last-position logits yield the first token and the
            slot's rows arm. Non-final chunks set the same rows
            (harmless while active stays False) so one program covers
            every chunk; everything but the token buffer is traced.
            A diffusion model's admission (`blocked`: the prompt's
            tail, the request's steps and threshold) yields no token:
            the chunk is the prompt's whole blocks, the slot's first
            block starts where they end with the tail at its head,
            and `limit` is the position after the last one owed."""
            pages = carry["pages"].at[slot].set(row)
            cache, logits, *_ = chunk_fn(params, adapters, carry["cache"],
                                         row, tokens, t0, clen)
            key = jax.random.fold_in(jax.random.key(seed), plen)
            first = pick(logits[0], temp, key)
            if B:
                tail, steps, thr = blocked
                start = plen // B * B
                masked = jnp.arange(B) >= plen - start
                return {
                    "cache": cache,
                    "pages": pages,
                    "pos": carry["pos"].at[slot].set(start),
                    "tok": carry["tok"],
                    "active": carry["active"].at[slot].set(final),
                    "temp": carry["temp"].at[slot].set(temp),
                    "seed": carry["seed"].at[slot].set(seed),
                    "limit": carry["limit"].at[slot].set(limit),
                    "plen": carry["plen"].at[slot].set(plen),
                    "blk": carry["blk"].at[slot].set(
                        jnp.where(masked, model.mask_id, tail)),
                    "msk": carry["msk"].at[slot].set(masked),
                    "fwd": carry["fwd"].at[slot].set(0),
                    "steps": carry["steps"].at[slot].set(steps),
                    "thr": carry["thr"].at[slot].set(thr),
                }, first
            # active iff this was the last chunk, the first token did
            # not end it, and there is budget left (limit = plen +
            # max_new - 1: the position after which no step token is owed)
            active = final & (first != eos) & (plen < limit)
            out = {
                "cache": cache,
                "pages": pages,
                "pos": carry["pos"].at[slot].set(plen),
                "tok": carry["tok"].at[slot].set(first),
                "active": carry["active"].at[slot].set(active),
                "temp": carry["temp"].at[slot].set(temp),
                "seed": carry["seed"].at[slot].set(seed),
                "limit": carry["limit"].at[slot].set(limit),
            }
            if self._spec_on:
                # the chunk's real tokens land in the slot's history
                # row (the n-gram draft source); padded tail indices
                # point past max_len and are dropped by the scatter
                cidx = jnp.arange(tokens.shape[1])
                hidx = jnp.where(cidx < clen, t0 + cidx, max_len_)
                out["hist"] = carry["hist"].at[slot, hidx].set(
                    tokens[0])
            return out, first

        def _admit_many(params, adapters, carry, tokens, t0s, clens,
                        slots, rows, temps, seeds, limits, finals,
                        plens):
            """admit_batch > 1: B same-bucket prefill chunks through
            ONE batched chunk program (llm/decode.py chunk_batch) —
            page reservations were already claimed host-side in one
            critical section; this is the device half. PAD rows
            (batch padded to its pow2 bucket) carry slot == n_slots,
            which every per-slot scatter DROPS (out-of-range scatter
            indices are discarded under jit), an all-zero page row
            (writes land on the null page) and clen 0."""
            pages = carry["pages"].at[slots].set(rows)
            cache, logits, *_ = chunk_batch_fn(
                params, adapters, carry["cache"], rows, tokens,
                t0s, clens)
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.key(s), p))(
                    seeds, plens)
            firsts = pick(logits, temps, keys)
            actives = finals & (firsts != eos) & (plens < limits)
            out = {
                "cache": cache,
                "pages": pages,
                "pos": carry["pos"].at[slots].set(plens),
                "tok": carry["tok"].at[slots].set(firsts),
                "active": carry["active"].at[slots].set(actives),
                "temp": carry["temp"].at[slots].set(temps),
                "seed": carry["seed"].at[slots].set(seeds),
                "limit": carry["limit"].at[slots].set(limits),
            }
            if self._spec_on:
                cidx = jnp.arange(tokens.shape[1])[None, :]
                hidx = jnp.where(cidx < clens[:, None],
                                 t0s[:, None] + cidx, max_len_)
                out["hist"] = carry["hist"].at[
                    slots[:, None], hidx].set(tokens)
            return out, firsts

        def _step_all(params, adapters, carry):
            """Advance every slot one token through ONE program: sample or
            argmax the next token per slot, advance active positions,
            retire on budget or eos — ON DEVICE. The active mask rides
            INTO the forward: an inactive slot's stale page-table entry
            may point at a page re-allocated to another request, so its
            garbage write is redirected to the null page."""
            active, temp = carry["active"], carry["temp"]
            cache, logits, *_ = paged_step(
                params, adapters, carry["cache"], carry["pages"],
                carry["pos"], carry["tok"], active)
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.key(s), p + 1))(
                    carry["seed"], carry["pos"])
            nxt = pick(logits, temp, keys)
            pos2 = jnp.where(active, carry["pos"] + 1, carry["pos"])
            act2 = active & (pos2 < carry["limit"]) & (nxt != eos)
            out = {
                "cache": cache,
                "pages": carry["pages"],
                "pos": pos2,
                "tok": jnp.where(active, nxt, carry["tok"]),
                "active": act2,
                "temp": temp,
                "seed": carry["seed"],
                "limit": carry["limit"],
            }
            if self._spec_on:
                out["hist"] = carry["hist"]
            # emitted token per slot + the entry mask saying which are real
            return out, (nxt, active)

        spec_c = self._spec_k + 1

        def _spec_all(params, adapters, carry):
            """Speculative iteration, ALL slots: self-draft spec_k
            tokens from each slot's own history (ngram_propose),
            verify the whole window [tok, d1..dk] in ONE target
            forward over the paged cache, emit the longest prefix
            the target itself would have produced. By construction
            the emitted stream is token-identical to plain decode:
            token i is only accepted when every input before it was
            the target's own pick, so its logits — and therefore
            its pick, greedy or seeded — are exactly the plain
            path's. Rejected positions' K/V writes are garbage, and
            the rollback is positional: pos advances only past
            accepted tokens, so the NEXT window re-writes those
            very pages before anything can attend to them."""
            s_idx = jnp.arange(S)
            pos, tok = carry["pos"], carry["tok"]
            active, temp = carry["active"], carry["temp"]
            # the current token is real history at its write position
            # — anchor it before drafting so the trailing n-gram
            # includes it. INACTIVE slots write nothing (index
            # max_len drops): their pos/tok are stale, and a slot
            # mid-chunked-admission shares this hist buffer — a
            # stale write could corrupt the incoming prompt's
            # history and poison its draft anchors (never its
            # output; drafts are proposals)
            hist = carry["hist"].at[
                s_idx, jnp.where(active, pos, max_len_)].set(tok)
            drafts = ngram_propose(hist, pos, spec_c - 1)
            inputs = jnp.concatenate([tok[:, None], drafts], axis=1)
            widx = pos[:, None] + jnp.arange(spec_c)
            # record the window inputs (accepted ones are permanent
            # history; rejected ones sit past the new pos and are
            # overwritten before the draft matcher can anchor on
            # them); inactive slots and out-of-range indices drop
            hist = hist.at[
                s_idx[:, None],
                jnp.where(active[:, None] & (widx < max_len_),
                          widx, max_len_)].set(inputs)
            cache, logits, *_ = paged_verify(
                params, adapters, carry["cache"], carry["pages"],
                pos, inputs, active)
            # the SAME rng schedule as the plain step (fold_in at
            # write-position + 1) — seeded sampling stays pinned
            # across spec on/off
            keys = jax.vmap(
                lambda s, p: jax.vmap(
                    lambda q: jax.random.fold_in(
                        jax.random.key(s), q + 1))(
                            p + jnp.arange(spec_c)))(
                                carry["seed"], pos)
            # THE pick (greedy/sampled select), vmapped over the
            # window axis — one selection implementation, so the
            # spec-on == spec-off identity can't drift from a
            # future pick() edit
            g = jax.vmap(pick, in_axes=(1, None, 1),
                         out_axes=1)(logits, temp, keys)
            # token i is emitted iff every input before it was the
            # target's own pick, nothing before it ended the
            # request, and the budget has room — the in-jit
            # statement of greedy-exact acceptance
            emits = [active]
            for i in range(1, spec_c):
                emits.append(emits[-1]
                             & (inputs[:, i] == g[:, i - 1])
                             & (g[:, i - 1] != eos)
                             & (pos + i < carry["limit"]))
            emit = jnp.stack(emits, axis=1)
            n_acc = emit.sum(axis=1).astype(jnp.int32)
            last = g[s_idx, jnp.maximum(n_acc - 1, 0)]
            pos2 = jnp.where(active, pos + n_acc, pos)
            tok2 = jnp.where(active, last, tok)
            act2 = active & (pos2 < carry["limit"]) & (last != eos)
            out = {"cache": cache, "pages": carry["pages"],
                   "pos": pos2, "tok": tok2, "active": act2,
                   "temp": temp, "seed": carry["seed"],
                   "limit": carry["limit"], "hist": hist}
            return out, (g, jnp.where(active, n_acc, 0))

        def _block_all(params, adapters, carry):
            """A diffusion model's iteration, ALL slots: one forward over
            every live slot's current block (B positions at the block's
            start `pos`, `<|MASK|>` where still masked, over the cached
            earlier blocks; the block's K/V written at every forward),
            then ON DEVICE: each masked position's pick and its
            probability (the confidence), the unmask rule, the commit,
            the advance and retirement.

            A block that ENTERS with nothing masked is committed by this
            forward (its K/V are now those of its final tokens) and
            gives way to the next: `pos` moves on, every position is
            masked again, the forward index returns to 0. Otherwise the
            `ceil(B / steps)` most confident masked positions (ties to
            the earlier) and every one over `thr` are unmasked for good.
            The run of final tokens from the block's start is what the
            host streams; the slot retires when that run reaches the
            budget (`limit`) or holds an `eos` among the positions it
            owes, so a request's last block is never committed: nothing
            would read it."""
            active, pos = carry["active"], carry["pos"]
            blk, msk, temp = carry["blk"], carry["msk"], carry["temp"]
            cache, logits, *counted = paged_verify(
                params, adapters, carry["cache"], carry["pages"], pos,
                blk, active)
            with layer_scope("unmask"):
                j = jnp.arange(B)
                posj = pos[:, None] + j[None, :]                   # [S, B]
                commit = active & ~jnp.any(msk, axis=1)
                keys = jax.vmap(lambda s, ps, f: jax.vmap(
                    lambda q: jax.random.fold_in(jax.random.fold_in(
                        jax.random.key(s), q + 1), f))(ps))(
                            carry["seed"], posj, carry["fwd"])
                g = jax.vmap(pick, in_axes=(1, None, 1), out_axes=1)(
                    logits, temp, keys)
                lf = logits.astype(jnp.float32)
                conf = jnp.exp(
                    jnp.take_along_axis(lf, g[..., None], -1)[..., 0]
                    - jax.nn.logsumexp(lf, axis=-1))
                cm = jnp.where(msk, conf, -1.0)
                # a masked position's rank by confidence among the masked
                ahead = (cm[:, None, :] > cm[:, :, None]) | (
                    (cm[:, None, :] == cm[:, :, None])
                    & (j[None, None, :] < j[None, :, None]))
                rank = jnp.sum(ahead, axis=-1)
                least = -(-B // jnp.maximum(carry["steps"], 1))
                sel = (msk & active[:, None]
                       & ((rank < least[:, None])
                          | (cm > carry["thr"][:, None])))
                blk2 = jnp.where(sel, g, blk)
                msk2 = msk & ~sel
                run = jnp.sum(jnp.cumprod(~msk2, axis=1), axis=1)
                owed = ((posj >= carry["plen"][:, None])
                        & (posj < carry["limit"][:, None])
                        & (j[None, :] < run[:, None]))
                retire = ~commit & (
                    jnp.any(owed & (blk2 == eos), axis=1)
                    | (pos + run >= carry["limit"]))
                out = {
                    "cache": cache,
                    "pages": carry["pages"],
                    "pos": jnp.where(commit, pos + B, pos),
                    "tok": carry["tok"],
                    "active": active & ~retire,
                    "temp": temp,
                    "seed": carry["seed"],
                    "limit": carry["limit"],
                    "plen": carry["plen"],
                    "blk": jnp.where(commit[:, None], model.mask_id, blk2),
                    "msk": msk2 | commit[:, None],
                    "fwd": jnp.where(commit, 0, carry["fwd"] + 1),
                    "steps": carry["steps"],
                    "thr": carry["thr"],
                }
            sown = counted[0] if counted else {}
            return out, (blk2, msk2, conf, sel, carry["fwd"], active,
                         commit, {k: sown[k] for k in (
                             "moe_pairs", "moe_experts_live") if k in sown})

        # the carry is DONATED: the cache never round-trips host<->device
        # and XLA may update the slot rows in place. On an mp mesh the
        # carry's output shardings are PINNED (cache on the heads split,
        # scalars-per-slot replicated): donation requires the output
        # buffer to reuse the input's layout, and an XLA-chosen resharding
        # would silently turn the in-place update into a full copy.
        self._spec_jit = None
        self._admit_many_jit = None
        self._block_jit = None
        # track_jit: retrace telemetry + the XLA cost/memory ledger — each
        # program's cost_analysis/memory_analysis lands in xla.program.*
        # gauges on first compile (utils/xla_ledger.py)
        if mesh is None:
            self._admit_jit = _mx.track_jit(
                jax.jit(_admit, donate_argnums=(2,)), "engine_admit")
            self._step_jit = _mx.track_jit(
                jax.jit(_step_all, donate_argnums=(2,)), "engine_step")
            if B:
                self._block_jit = _mx.track_jit(
                    jax.jit(_block_all, donate_argnums=(2,)), "engine_block")
            if self._spec_on:
                self._spec_jit = _mx.track_jit(
                    jax.jit(_spec_all, donate_argnums=(2,)), "engine_spec")
            if self._admit_batch > 1:
                self._admit_many_jit = _mx.track_jit(jax.jit(
                    _admit_many, donate_argnums=(2,)), "engine_admit_many")
            carry_sh = None
        else:
            # ONE carry-layout dict, used for the jit out_shardings AND the
            # initial placement below — two copies drifting apart (a new
            # carry key updated in only one) would silently turn the
            # donated in-place update into a full cache copy
            carry_sh = {
                "cache": {"k": kv_sharding, "v": kv_sharding},
                "pages": rep_sharding,
                "pos": rep_sharding, "tok": rep_sharding,
                "active": rep_sharding, "temp": rep_sharding,
                "seed": rep_sharding, "limit": rep_sharding,
            }
            if self._quant:
                scale_sharding = NamedSharding(
                    mesh, partition.paged_kv_scale_spec("mp"))
                carry_sh["cache"]["ks"] = scale_sharding
                carry_sh["cache"]["vs"] = scale_sharding
            if self._spec_on:
                carry_sh["hist"] = rep_sharding
            self._admit_jit = _mx.track_jit(jax.jit(
                _admit, donate_argnums=(2,),
                out_shardings=(carry_sh, rep_sharding)), "engine_admit")
            self._step_jit = _mx.track_jit(jax.jit(
                _step_all, donate_argnums=(2,),
                out_shardings=(carry_sh, (rep_sharding, rep_sharding))),
                "engine_step")
            if self._spec_on:
                self._spec_jit = _mx.track_jit(jax.jit(
                    _spec_all, donate_argnums=(2,),
                    out_shardings=(carry_sh,
                                   (rep_sharding, rep_sharding))),
                    "engine_spec")
            if self._admit_batch > 1:
                self._admit_many_jit = _mx.track_jit(jax.jit(
                    _admit_many, donate_argnums=(2,),
                    out_shardings=(carry_sh, rep_sharding)),
                    "engine_admit_many")

        head = model.head_dim or model.d_model // model.n_heads
        z = (model.n_layers, self._n_pages, self._page_size,
             model.n_kv_heads or model.n_heads, head)
        pool_dtype = jnp.int8 if self._quant else kv_dtype
        if latent:
            # one row a token and no heads: the compressed key/value and
            # the indexer's key (llm/decode.py make_paged_latent_decode)
            cache = {"kv": jnp.zeros(z[:3] + (latent.width,), kv_dtype),
                     "ik": jnp.zeros(z[:3] + (latent.index_dim,), kv_dtype)}
        else:
            cache = {"k": jnp.zeros(z, pool_dtype),
                     "v": jnp.zeros(z, pool_dtype)}
        if self._quant:
            zs = z[:2] + z[3:4]
            cache["ks"] = jnp.zeros(zs, jnp.float32)
            cache["vs"] = jnp.zeros(zs, jnp.float32)
        # persistent KV bytes amortized per decode slot — THE density
        # figure int8 paging halves (scales included: they are the
        # quantized layout's real, small, overhead)
        kv_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in cache.values())
        _mx.set_gauge("serving.kv_bytes_per_slot", kv_bytes // S)
        self._carry = {
            "cache": cache,
            "pages": jnp.zeros((S, self._max_pages), jnp.int32),
            "pos": jnp.zeros((S,), jnp.int32),
            "tok": jnp.zeros((S,), jnp.int32),
            "active": jnp.zeros((S,), bool),
            "temp": jnp.zeros((S,), jnp.float32),
            "seed": jnp.zeros((S,), jnp.uint32),
            "limit": jnp.zeros((S,), jnp.int32),
        }
        if B:
            self._carry.update(
                plen=jnp.zeros((S,), jnp.int32),
                blk=jnp.zeros((S, B), jnp.int32),
                msk=jnp.zeros((S, B), bool),
                fwd=jnp.zeros((S,), jnp.int32),
                steps=jnp.ones((S,), jnp.int32),
                thr=jnp.full((S,), 2.0, jnp.float32))
        if self._spec_on:
            # per-slot token history (prompt + generated): the draft
            # source, written by admission chunks and the verify
            # windows. Prefix-HIT positions are skipped by chunked
            # prefill and may retain a previous occupant's tokens —
            # draft anchors landing there cost acceptance, never
            # correctness (the verify forward decides)
            self._carry["hist"] = jnp.zeros((S, self.max_len), jnp.int32)
        if carry_sh is not None:
            # place the persistent carry on the mesh up front — every later
            # call donates it back in the same layout
            self._carry = jax.tree.map(
                lambda a, s: jax.device_put(a, s), self._carry, carry_sh)

        # device-memory ledger: the engine's three resident pytrees. The
        # kv_pool entry must agree with the kv_bytes_per_slot math above
        # within 1% (pinned in tests) — they sum the same buffers
        _ledger.register_buffers("serving_params", self.params)
        _ledger.register_buffers("kv_pool", self._carry["cache"])
        _ledger.register_buffers("engine_carry",
                                 {k: v for k, v in self._carry.items()
                                  if k != "cache"})

        self._cond = threading.Condition()
        self._waiting: deque[_Request] = deque()
        self._free: list[int] = list(range(S))
        self._slots: list[Optional[_SlotState]] = [None] * S
        self._stopping = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._version = 0
        self._pending_swap: Optional[_Swap] = None
        _mx.set_gauge("serving.model_version", 0)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "DecodeEngine":
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-engine")
        self._thread.start()
        return self

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Refuse new submits and wait (bounded) for every ACCEPTED
        request — decoding slots and queued ones — to finish. One-way:
        a drained engine only goes on to stop(). Returns False when the
        deadline expired with work still in flight (stop() then errors
        those tickets as before — the drain was best-effort, bounded)."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            deadline = time.monotonic() + timeout_s
            while self._waiting or any(s is not None for s in self._slots):
                if (self._stopping or self._thread is None
                        or not self._thread.is_alive()):
                    break
                left = deadline - time.monotonic()
                if left <= 0:
                    _mx.inc("serving.engine.drain_timeouts")
                    return False
                self._cond.wait(min(0.1, left))
        return True

    def stop(self, drain: bool = False,
             drain_timeout_s: float = 30.0) -> None:
        """Tear the engine down. `drain=True` first lets in-flight slots
        finish (bounded by `drain_timeout_s`) so a scale-down or rolling
        replica swap never errors a request that was already decoding;
        whatever is still in flight when the deadline expires is errored
        as before."""
        if drain and self._thread is not None and self._thread.is_alive():
            self.drain(drain_timeout_s)
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
        self._fail_outstanding(RuntimeError("decode engine stopped"))

    # ------------------------------------------------------------- hot swap
    @property
    def model_version(self) -> int:
        return self._version

    def swap_adapters(self, adapters: Pytree,
                      version: Optional[int] = None,
                      timeout: float = 60.0) -> int:
        """Hot-swap the LoRA adapter VALUES the engine serves — applied
        by the engine thread between decode iterations, so no step ever
        mixes versions, the persistent KV cache survives untouched, and
        no program retraces (adapters are replicated per the
        partition.TABLES["lora"] contract; structure/shape/dtype changes
        are refused — see check_adapter_swap). In-flight requests finish
        on the new adapters from their next step. Returns the new
        monotonic `model_version` (default: current + 1)."""
        if self.adapters is None:
            raise ValueError(
                "this engine was built without adapters — hot swap "
                "replaces adapter VALUES only (the compiled programs' "
                "signature is fixed at construction); deploy the replica "
                "with adapters (zero-initialized LoRA serves the base "
                "model exactly) to enable rolling updates")
        with self._cond:
            stacked, ver = prepare_adapter_swap(
                self.adapters, adapters, self.model.n_layers,
                self._version, version)
            if self.mesh is not None:
                from ..parallel import partition

                stacked = partition.shard_params(stacked, self.mesh,
                                                 "lora")
            if self._pending_swap is not None:
                raise RuntimeError(
                    "an adapter swap is already pending — serialize "
                    "swaps (the rolling updater does)")
            swap = _Swap(stacked, ver)
            running = (self._thread is not None and self._thread.is_alive()
                       and not self._stopping)
            if running:
                self._pending_swap = swap
                self._cond.notify_all()
        if not running:
            # no decode thread -> no iteration boundary to respect; the
            # per-request degrade path still serves the new values
            self._apply_swap(swap)
            return self._version
        if not swap.applied.wait(timeout):
            raise TimeoutError(f"adapter swap not applied in {timeout}s")
        if swap.error is not None:
            raise swap.error
        return self._version

    def _apply_swap(self, swap: _Swap) -> None:
        """Engine-thread (or stopped-engine) application point: ONE
        attribute assignment between jit dispatches — the next admit/step
        call reads the new tree; nothing about the carry changes."""
        with recorder.span("serving.swap", version=swap.version):
            self.adapters = swap.adapters
            self._version = swap.version
        _mx.set_gauge("serving.model_version", swap.version)
        _mx.inc("serving.engine.swaps")
        swap.applied.set()

    # ------------------------------------------------------------ admission
    def submit(self, tokens, max_new_tokens: int,
               temperature: float = 0.0,
               seed: Optional[int] = None,
               denoising_steps: Optional[int] = None,
               confidence_threshold: Optional[float] = None) -> Ticket:
        """Queue one prompt; returns the Ticket its tokens stream to.
        Capacity contract: prompt + max_new_tokens <= max_len (exact — the
        engine never buckets the token budget). A block-diffusion model
        takes `denoising_steps` (1 .. its block length, which is the
        default: at least one token a forward) and `confidence_threshold`
        (in (0, 1]: every masked position over it is unmasked too; None:
        the static rule); any other model refuses both."""
        steps, threshold = self.denoising(denoising_steps,
                                          confidence_threshold)
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise InvalidRequest(
                "tokens must contain at least one prompt token")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise InvalidRequest(
                f"max_new_tokens must be >= 1; got {max_new}")
        if not self.admissible(len(tokens), max_new):
            raise InvalidRequest(self.capacity_error(len(tokens), max_new))
        if seed is None:
            import random as _random

            seed = _random.getrandbits(31)
        # the per-slot seed rides as a device uint32 — mask client-supplied
        # values into range instead of letting jnp.uint32 overflow on the
        # engine thread (still deterministic per seed)
        seed = int(seed) & 0xFFFFFFFF
        req = _Request(tokens, max_new, float(temperature), seed, steps,
                       threshold)
        with self._cond:
            if self._stopping or (self._thread is not None
                                  and not self._thread.is_alive()):
                raise RuntimeError("decode engine is stopped")
            if self._draining:
                raise RuntimeError(
                    "decode engine is draining (replica stopping) — "
                    "request refused")
            if self._thread is None:
                raise RuntimeError("decode engine not started "
                                   "(call .start())")
            self._waiting.append(req)
            _mx.set_gauge("serving.engine.queue", len(self._waiting))
            self._cond.notify_all()
        _mx.inc("serving.engine.requests")
        _submitted.ticket = req.ticket
        return req.ticket

    def denoising(self, steps, threshold) -> tuple:
        """A request's (denoising steps, confidence threshold) as the block
        program takes them, or InvalidRequest with the sentence why not."""
        B = self._block
        if not B:
            if steps is not None or threshold is not None:
                raise InvalidRequest(
                    "denoising_steps and confidence_threshold are a "
                    "block-diffusion model's parameters; this model "
                    "generates one token a step")
            return 0, None
        try:
            steps = B if steps is None else int(steps)
            threshold = None if threshold is None else float(threshold)
        except (TypeError, ValueError):
            raise InvalidRequest(
                "denoising_steps must be an integer and "
                "confidence_threshold a number or null") from None
        if not 1 <= steps <= B:
            raise InvalidRequest(
                f"denoising_steps must be 1 .. {B} (the model's block "
                f"length); got {steps}")
        if threshold is not None and not 0.0 < threshold <= 1.0:
            raise InvalidRequest(
                "confidence_threshold must lie in (0, 1] or be null (the "
                f"static rule); got {threshold}")
        return steps, threshold

    # -------------------------------------------------------------- capacity
    def admissible(self, prompt_len: int, max_new: int) -> bool:
        """THE engine capacity oracle: True iff a (prompt_len, max_new)
        request can ever be admitted: prompt + max_new <= max_len AND
        ceil((prompt + max_new) / page_size) <= the usable page budget.
        The predictor's routing consults this
        (not static max_len math) so a request the page budget refuses
        falls back to the per-request path instead of 400ing, and one
        paging admits is never degraded into a per-request 400."""
        prompt_len, max_new = int(prompt_len), int(max_new)
        if prompt_len + max_new > self.max_len:
            return False
        need = -(-(prompt_len + max_new) // self._page_size)
        return need <= self._usable

    def capacity_error(self, prompt_len: int, max_new: int) -> str:
        """The message submit() raises for an inadmissible request —
        states the page math so a 400 is actionable."""
        tot = prompt_len + max_new
        need = -(-tot // self._page_size)
        return (f"prompt {prompt_len} + max_new_tokens {max_new} = {tot} "
                f"tokens needs ceil({tot}/{self._page_size}) = {need} KV "
                f"pages, but the engine budget is {self._usable} usable "
                f"pages (kv_n_pages {self._n_pages} minus the reserved "
                f"null page) with per-request cap max_len {self.max_len} "
                "(capacity contract: prompt + max_new_tokens <= "
                "max_len AND ceil((prompt + max_new_tokens) / "
                "kv_page_size) <= kv_n_pages - 1)")

    # ------------------------------------------------------- introspection
    @property
    def kv_page_size(self) -> int:
        """Page size of the KV pool — advertised on /info so the
        gateway's prefix-affinity hash uses the replica's real page
        geometry."""
        return self._page_size

    def prefix_digests(self, limit: int = 64) -> list:
        """Hex digests of resident FIRST-page prefix-cache keys — the
        residency summary replicas advertise for gateway prefix-affinity
        routing (serving/scheduler.py). First-page keys only: the
        gateway hashes a prompt's leading page-aligned block, so deeper
        chain keys could never match its probe. Read lock-free off the
        engine-thread-owned prefix map: the advertised set is a routing
        HINT — a stale entry costs one least-loaded fallback, never
        correctness."""
        if not self._prefix_on:
            return []
        out = []
        for key, ent in list(self._prefix.items()):
            if ent.parent is None:
                out.append(key.hex())
                if len(out) >= limit:
                    break
        return out

    def program_counts(self) -> dict:
        """Live compiled-program counts: {"step": 1, "admit": <=
        log2(prefill_chunk) + 1} in steady state — the retrace guard tests
        pin. "admit" is the chunk program (chunks are prefill_chunk-sized
        except a final pow2-bucketed remainder)."""
        pairs = [("step", self._step_jit), ("admit", self._admit_jit)]
        if self._block_jit is not None:
            # a diffusion model's iteration; "step" then stays 0
            pairs.append(("block", self._block_jit))
        if self._spec_jit is not None:
            # spec mode replaces the step dispatch with ONE verify-window
            # program; "step" then stays 0 and "verify" must stay 1
            pairs.append(("verify", self._spec_jit))
        if self._admit_many_jit is not None:
            # admit_batch > 1 replaces the per-admission chunk dispatch:
            # bounded by chunk buckets x pow2 batch buckets
            pairs.append(("admit_batch", self._admit_many_jit))
        return {name: fn._cache_size() for name, fn in pairs}

    # ------------------------------------------------------------ engine loop
    def _loop(self) -> None:
        # frames: ("admit", slot, first_token_dev) | ("step", toks, mask)
        # | ("spec", toks, counts) | ("block", *what _block_all yields)
        pending: deque[tuple] = deque()
        try:
            while True:
                with self._cond:
                    if self._stopping:
                        break
                    swap, self._pending_swap = self._pending_swap, None
                    idle = (swap is None and not self._waiting and not pending
                            and all(s is None for s in self._slots))
                    if idle:
                        self._cond.wait(0.2)
                        continue
                if swap is not None:
                    # between iterations, by construction: the previous
                    # iteration's dispatches hold their own references,
                    # every later one reads the new tree
                    self._apply_swap(swap)
                self._advance_admissions(pending)
                # step when any occupied slot is past admission — a slot
                # mid-chunked-prefill is inert on device, and a step over
                # ONLY such slots would be a wasted dispatch
                admitting = {a.slot for a in self._admissions}
                if any(s is not None and i not in admitting
                       for i, s in enumerate(self._slots)):  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
                    if self._block:
                        # one forward advances every slot's current block
                        self._carry, frame = self._block_jit(
                            self.params, self.adapters, self._carry)
                        pending.append(("block",) + frame)
                    elif self._spec_on:
                        # one verify window advances every slot up to
                        # spec_k + 1 tokens — the speculative analog of
                        # the plain step, same dispatch-ahead contract
                        self._carry, (toks, counts) = self._spec_jit(
                            self.params, self.adapters, self._carry)
                        pending.append(("spec", toks, counts))
                    else:
                        self._carry, (toks, mask) = self._step_jit(
                            self.params, self.adapters, self._carry)
                        pending.append(("step", toks, mask))
                # drain: normally keep `fetch_chunk` frames in flight so
                # host bookkeeping overlaps device steps; drain eagerly
                # when requests are starved for a slot (a completion frees
                # one) or nothing new was dispatched
                with self._cond:
                    starved = bool(self._waiting) and not self._free
                eager = starved or all(s is None for s in self._slots)  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
                while pending and (eager
                                   or len(pending) >= self.fetch_chunk):
                    self._drain(pending.popleft())
        except BaseException as e:  # noqa: BLE001 — fail tickets, not silently
            log.exception("decode engine loop died")
            _mx.inc("serving.engine.errors")
            # mark stopped FIRST so submit() refuses (and the predictor
            # falls back to the per-request path) instead of queueing
            # tickets nothing will ever complete
            with self._cond:
                self._stopping = True
            self._fail_outstanding(
                RuntimeError(f"decode engine failed: {type(e).__name__}: {e}"))

    # ----------------------------------------------------- admission plane
    # All of the page machinery below runs on the ENGINE THREAD only
    # (_advance_admissions from the loop, _release_slot_pages via _drain's
    # _deliver) — the free list and prefix map need no lock; _cond still
    # guards the _waiting/_free/_slots handoff with submit()/stop().
    # THREAD-OWNERSHIP NOTE (the justification behind the per-line
    # lock-discipline suppressions in this file): `_slots` ENTRIES are
    # read and replaced only by the engine thread; the one lock-guarded
    # cross-thread writer, _fail_outstanding, runs after the loop has
    # exited (crash path) or after stop() joined the thread — the _cond
    # handoff in stop()/submit() is the happens-before edge. graftlint
    # still flags every bare access so a NEW cross-thread writer cannot
    # creep in unreviewed (ISSUE 13).

    def _next_tick(self) -> int:
        self._ticks += 1
        return self._ticks

    def _prefix_lookup(self, toks: list[int]):
        """(chain keys for every FULL prompt page, resident hit entries).
        The hit walk is capped at (prompt_len - 1) // page_size pages so
        at least the prompt's last token is always prefilled — the
        first-token logits must be computed, not remembered."""
        ps = self._page_size
        keys: list[bytes] = []
        key = b"\x00"
        for i in range(len(toks) // ps):
            key = _page_key(key, toks[i * ps:(i + 1) * ps])
            keys.append(key)
        hits: list[_PrefixEntry] = []
        if self._prefix_on:
            for i in range((len(toks) - 1) // ps):
                e = self._prefix.get(keys[i])
                if e is None:
                    break
                hits.append(e)
        return keys, hits

    def _alloc(self, n: int) -> Optional[list[int]]:
        """Pop `n` pages from the free list, evicting LRU leaf prefix
        entries (refs == 0, kids == 0) under pressure. None = the pool is
        pinned by in-flight requests right now — the caller re-queues and
        retries after a retirement frees pages."""
        short = n - len(self._free_pages)
        if short > 0:
            # the evictable leaves by age, gathered ONCE: a scan of the map
            # for every page freed held the engine thread for tens of
            # seconds once a 32,768-page pool was full of 1,000-page
            # documents (PERF.md section 6, PR 34)
            leaves = [(e.tick, k) for k, e in self._prefix.items()
                      if e.refs == 0 and e.kids == 0]
            heapq.heapify(leaves)
            while short > 0 and leaves:
                _tick, vkey = heapq.heappop(leaves)
                victim = self._prefix.pop(vkey)
                parent = (self._prefix.get(victim.parent)
                          if victim.parent is not None else None)
                if parent is not None:
                    parent.kids -= 1
                    if parent.kids == 0 and parent.refs == 0:
                        heapq.heappush(leaves, (parent.tick, victim.parent))
                self._free_pages.append(victim.page)
                _mx.inc("serving.prefix_evictions")
                short -= 1
            if short > 0:
                return None
        pages = [self._free_pages.pop() for _ in range(n)]
        _mx.set_gauge("serving.kv_pages_free", len(self._free_pages))
        return pages

    def _release_slot_pages(self, st: _SlotState) -> None:
        """Retirement's page bookkeeping: drop this slot's refs on shared
        prefix pages (they STAY resident — evictable, reusable) and return
        its private pages to the free list."""
        for e in st.entries:
            e.refs -= 1
        self._free_pages.extend(st.private)
        st.entries, st.private = [], []
        _mx.set_gauge("serving.kv_pages_free", len(self._free_pages))

    def _start_admissions(self) -> None:
        """Claim (slot, pages) for waiting requests, FIFO. A request whose
        pages are currently pinned goes back to the queue HEAD — later
        requests do not overtake it (starvation beats reordering), and
        liveness holds because submit() already proved the request fits
        the total budget: whatever is pinned now retires eventually."""
        while True:
            with self._cond:
                if not (self._free and self._waiting):
                    return
                req = self._waiting.popleft()
                slot = self._free.pop()
                # claim in the SAME critical section as the pop (stop()
                # racing an admission must find the request somewhere)
                self._slots[slot] = _SlotState(req, self._block)
                _mx.set_gauge("serving.engine.queue", len(self._waiting))
            ps = self._page_size
            # with the prefix cache off there is nothing to look up OR
            # register — skip the per-page hashing entirely, and leave
            # the hit/miss counters untouched (a disabled cache reporting
            # a 0% hit rate on `top` reads as a cache problem, not a knob)
            keys, hits = (self._prefix_lookup(req.tokens)
                          if self._prefix_on else ([], []))
            total = -(-(len(req.tokens) + req.max_new) // ps)
            # hold the hit refs BEFORE allocating: _alloc evicts refs==0
            # entries under pressure, and evicting the very pages this
            # admission just looked up would leave its page row pointing
            # at freed (soon re-owned) pages — cross-request contamination
            now = self._next_tick()
            for e in hits:
                e.refs += 1
                e.tick = now
            fresh = self._alloc(total - len(hits))
            if fresh is None:
                for e in hits:
                    e.refs -= 1
                with self._cond:
                    self._slots[slot] = None
                    self._free.append(slot)
                    self._waiting.appendleft(req)
                    _mx.set_gauge("serving.engine.queue",
                                  len(self._waiting))
                return
            st = self._slots[slot]  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
            st.entries = list(hits)
            st.private = list(fresh)
            row = np.zeros(self._max_pages, np.int32)
            row[:len(hits)] = [e.page for e in hits]
            row[len(hits):total] = fresh
            req.ticket.prefill["hit_pages"] = len(hits)
            _mx.inc("serving.prompt_tokens", len(req.tokens))
            _mx.inc("serving.prefix_hit_tokens", len(hits) * ps)
            if hits:
                _mx.inc("serving.prefix_hits")
            elif self._prefix_on:
                _mx.inc("serving.prefix_misses")
            self._admissions.append(_Admission(
                req, slot, row, len(hits) * ps, keys, len(hits), total))

    def _advance_admissions(self, pending: deque) -> None:
        """ONE prefill chunk per engine iteration, round-robin across
        in-flight admissions — decode steps interleave between chunks
        (active slots keep advancing through a long prompt's prefill) and
        a short prompt admitted beside a long one reaches its first token
        after its OWN chunks, not the long one's. With admit_batch > 1,
        up to that many SAME-BUCKET admissions advance through one
        batched chunk program instead."""
        self._start_admissions()
        if not self._admissions:
            return
        if self._admit_batch > 1:
            self._advance_admissions_batched(pending)
            return
        adm = self._admissions.popleft()
        req = adm.req
        plen = len(req.tokens)
        B = self._block
        # what admission writes: the prompt, or of a diffusion model's the
        # whole blocks (its tail opens the slot's first block; a prompt
        # shorter than a block admits through one empty chunk)
        end = plen // B * B if B else plen
        cap = self._prefill_chunk or self.max_len
        clen = min(cap, end - adm.t0)
        # chunk buffers bucket to powers of two below the chunk cap, so
        # the remainder chunk reuses a bounded program set
        cb = min(_bucket(clen, pow2_cap=cap), cap)
        blocked = ()
        if B:
            cb = -(-max(cb, 1) // B) * B        # whole blocks (cap is)
            tail = np.zeros((B,), np.int32)
            tail[:plen - end] = req.tokens[end:]
            blocked = (jnp.asarray(tail), jnp.int32(req.steps),
                       jnp.float32(2.0 if req.threshold is None
                                   else req.threshold))
        buf = np.zeros((1, cb), np.int32)
        buf[0, :clen] = req.tokens[adm.t0:adm.t0 + clen]
        final = adm.t0 + clen == end
        limit = plen + req.max_new - (0 if B else 1)
        with recorder.span("serving.engine.admit", slot=adm.slot,
                           prompt=plen, t0=adm.t0, chunk=clen,
                           final=final):
            self._carry, first = self._admit_jit(
                self.params, self.adapters, self._carry,
                jnp.asarray(buf), jnp.int32(adm.t0), jnp.int32(clen),
                jnp.int32(adm.slot), jnp.asarray(adm.row),
                jnp.float32(req.temperature), jnp.uint32(req.seed),
                jnp.int32(limit), jnp.bool_(final), jnp.int32(plen),
                *blocked)
        _mx.inc("serving.engine.prefill_chunks")
        self._prefilled(req.ticket, final)
        self._count_keys(adm.t0, clen)
        if final:
            self._register_prefix(adm)
            if not B:       # a diffusion model's admission yields no token
                pending.append(("admit", adm.slot, first))
        else:
            adm.t0 += clen
            self._admissions.append(adm)

    def _advance_admissions_batched(self, pending: deque) -> None:
        """Batched admission (admit_batch > 1): pop up to admit_batch
        admissions whose NEXT chunk lands in the SAME pow2 chunk bucket
        and prefill them through ONE batched program — a burst of
        arrivals reaches first tokens in one device dispatch instead of
        one per request, which is where the TTFT p99 win lives. The
        batch axis pads to its own pow2 bucket so the program set stays
        bounded (chunk buckets x batch buckets); differently-bucketed
        admissions go back ahead of the queue, keeping round-robin
        order."""
        cap = self._prefill_chunk or self.max_len

        def next_bucket(adm):
            clen = min(cap, len(adm.req.tokens) - adm.t0)
            return min(_bucket(clen, pow2_cap=cap), cap)

        group = [self._admissions.popleft()]
        cb = next_bucket(group[0])
        skipped = []
        while self._admissions and len(group) < self._admit_batch:
            adm = self._admissions.popleft()
            if next_bucket(adm) == cb:
                group.append(adm)
            else:
                skipped.append(adm)
        self._admissions.extendleft(reversed(skipped))
        b = len(group)
        bb = 1
        while bb < b:
            bb *= 2
        toks = np.zeros((bb, cb), np.int32)
        rows = np.zeros((bb, self._max_pages), np.int32)
        t0s = np.zeros((bb,), np.int32)
        clens = np.zeros((bb,), np.int32)
        # PAD rows: slot n_slots — dropped by every scatter in the jit
        slots = np.full((bb,), self.n_slots, np.int32)
        temps = np.zeros((bb,), np.float32)
        seeds = np.zeros((bb,), np.uint32)
        limits = np.zeros((bb,), np.int32)
        finals = np.zeros((bb,), bool)
        plens = np.zeros((bb,), np.int32)
        for i, adm in enumerate(group):
            req = adm.req
            plen = len(req.tokens)
            clen = min(cap, plen - adm.t0)
            toks[i, :clen] = req.tokens[adm.t0:adm.t0 + clen]
            rows[i] = adm.row
            t0s[i], clens[i], slots[i] = adm.t0, clen, adm.slot
            temps[i], seeds[i] = req.temperature, req.seed
            limits[i] = plen + req.max_new - 1
            finals[i] = adm.t0 + clen == plen
            plens[i] = plen
        with recorder.span("serving.engine.admit", batch=b, chunk=cb):
            self._carry, firsts = self._admit_many_jit(
                self.params, self.adapters, self._carry,
                jnp.asarray(toks), jnp.asarray(t0s), jnp.asarray(clens),
                jnp.asarray(slots), jnp.asarray(rows),
                jnp.asarray(temps), jnp.asarray(seeds),
                jnp.asarray(limits), jnp.asarray(finals),
                jnp.asarray(plens))
        _mx.inc("serving.engine.prefill_chunks", b)
        _mx.observe("serving.engine.admit_batch", b)
        for i, adm in enumerate(group):
            self._prefilled(adm.req.ticket, bool(finals[i]))
            self._count_keys(adm.t0, int(clens[i]))
            if finals[i]:
                self._register_prefix(adm)
                pending.append(("admit", adm.slot, firsts[i]))
            else:
                adm.t0 += int(clens[i])
                self._admissions.append(adm)

    @staticmethod
    def _prefilled(ticket: Ticket, final: bool = True) -> None:
        """One prefill chunk of a request was dispatched; the FINAL one's
        dispatch ends its `serving.engine.prefill` span (what follows, to
        the first token, is `serving.engine.first_fetch`)."""
        ticket.prefill["chunks"] += 1
        if final:
            ticket.t_prefilled = time.perf_counter()

    def _register_prefix(self, adm: _Admission) -> None:
        """Publish the request's full prompt pages into the prefix map AT
        ADMISSION (not retirement): a concurrent identical prompt hits
        while this one still decodes — the system-prompt traffic shape.
        Full pages are immutable from here on (decode writes start at
        pos >= prompt_len, which lands strictly past them). A page whose
        key already exists (two identical prompts admitted concurrently)
        stays private — content-identical, so the resident entry serves
        future hits and ours is simply freed at retirement."""
        if not self._prefix_on:
            return
        st = self._slots[adm.slot]  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
        if st is None:   # raced a crash/stop reset
            return
        full = len(adm.req.tokens) // self._page_size
        for i in range(adm.hit_pages, full):
            if adm.keys[i] in self._prefix:
                continue
            page = int(adm.row[i])
            parent = adm.keys[i - 1] if i else None
            ent = _PrefixEntry(page, parent, self._next_tick())
            self._prefix[adm.keys[i]] = ent
            if parent is not None and parent in self._prefix:
                self._prefix[parent].kids += 1
            st.entries.append(ent)
            st.private.remove(page)

    # -------------------------------------------------------------- draining
    def _drain(self, frame: tuple) -> None:
        """Materialize one queued frame and route its tokens. This is the
        only host<->device sync point; the span measures the actual wait."""
        if frame[0] == "admit":
            _kind, slot, first = frame
            with recorder.span("serving.engine.fetch", kind="admit"):
                tok = int(np.asarray(first))
            self._deliver(slot, tok, first=True)
        elif frame[0] == "spec":
            # one verify window's yield: toks [S, spec_k+1] target picks,
            # counts [S] accepted lengths (0 = slot was inert)
            _kind, toks_dev, counts_dev = frame
            with recorder.span("serving.engine.fetch", kind="spec"):
                toks = np.asarray(toks_dev)
                counts = np.asarray(counts_dev)
            live = counts > 0
            self._count_step(live, window=toks.shape[1])
            if live.any():
                # every live slot consumed spec_k drafts and banked
                # count - 1 beyond the guaranteed token — the accept
                # rate `top` and the bench report
                _mx.inc("serving.spec.proposed",
                        int(live.sum()) * (toks.shape[1] - 1))
                _mx.inc("serving.spec.accepted",
                        int((counts[live] - 1).sum()))
            for slot in np.nonzero(live)[0]:
                for t in toks[slot, :counts[slot]]:
                    self._deliver(int(slot), int(t), first=False)
        elif frame[0] == "block":
            with recorder.span("serving.engine.fetch", kind="block"):
                toks, msk, conf, sel, fwd, live, commit, sown = (
                    jax.device_get(frame[1:]))
            self._drain_block(toks, msk, conf, sel, fwd, live, commit, sown)
        else:
            _kind, toks_dev, mask_dev = frame
            with recorder.span("serving.engine.fetch", kind="step"):
                toks = np.asarray(toks_dev)
                mask = np.asarray(mask_dev)
            self._count_step(mask)
            for slot in np.nonzero(mask)[0]:
                self._deliver(int(slot), int(toks[slot]), first=False)
        # publish the POST-delivery host occupancy, not the frame's entry
        # mask: with fetch_chunk=1 the final completing frame's entry mask
        # is >= 1 and no trailing all-inactive frame is ever dispatched —
        # an entry-mask gauge would read busy forever at idle
        _mx.set_gauge("serving.slots_active",
                      sum(s is not None for s in self._slots))  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)

    def _drain_block(self, toks, msk, conf, sel, fwd, live, commit,
                     sown) -> None:
        """One drained block frame: what `_block_all` left of every slot's
        block ([S, B] tokens, which are still masked, the picks'
        confidences, which were unmasked by this forward), the forward's
        index within its block, the slots that were live at its entry and
        those of them it committed. Counted first, as `_count_step` counts
        a step; then each live slot's run of final tokens from the block's
        start is delivered as far as it has grown, each token with the
        forward that unmasked it and its confidence."""
        B, ps = self._block, self._page_size
        slots = np.nonzero(live)[0]
        _mx.inc("serving.engine.steps")
        _mx.inc("serving.engine.slot_steps", len(slots))
        _mx.inc("serving.engine.block_forwards", len(slots))
        _mx.inc("serving.engine.commit_forwards", int(commit[slots].sum()))
        _mx.inc("serving.engine.block_positions", len(slots) * B)
        _mx.inc("serving.engine.unmasked_tokens", int(sel[slots].sum()))
        for name, v in sown.items():
            _mx.inc(f"serving.engine.{name}", int(v))
        pages = 0
        for slot in slots:
            st = self._slots[slot]  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
            if st is None:
                log.warning("engine: block frame for free slot %d dropped",
                            slot)
                continue
            pages += min(-(-(st.block_pos + B) // ps), self._max_pages)
            self._count_keys(st.block_pos, B)
            # the keys the window's B queries share: read once a forward
            _mx.inc("serving.engine.block_context", st.block_pos + B)
            if commit[slot]:
                st.block_pos, st.run, st.notes = st.block_pos + B, 0, {}
                continue
            for j in np.nonzero(sel[slot])[0]:
                st.notes[int(j)] = (int(fwd[slot]), float(conf[slot, j]))
            plen, req = len(st.req.tokens), st.req
            while st.run < B and not msk[slot, st.run]:
                j, p = st.run, st.block_pos + st.run
                st.run += 1
                # the prompt's tail at the first block's head is not owed
                if plen <= p < plen + req.max_new and self._deliver(
                        int(slot), int(toks[slot, j]), first=not st.out,
                        note=st.notes[j]):
                    break
        _mx.inc("serving.engine.page_steps", pages)

    def _count_step(self, live: np.ndarray, window: int = 1) -> None:
        """One drained step (or verify) frame, counted BEFORE its tokens
        are delivered: the program ran over every slot, the `live` ones
        were doing a request's work (their share over a run is the
        engine's slot occupancy). `page_steps` adds up the pages those
        slots' queries attended — prompt + emitted + the frame's `window`
        of queries, in pages — which over `steps x table_pages` is the
        share of the page table the paged kernel has to walk."""
        slots = np.nonzero(live)[0]
        _mx.inc("serving.engine.steps")
        _mx.inc("serving.engine.slot_steps", len(slots))
        pages = 0
        for slot in slots:
            st = self._slots[slot]  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
            if st is not None:
                # the frame's first query sits on the last emitted token
                first = len(st.req.tokens) + len(st.out) - 1
                pages += min(-(-(first + window) // self._page_size),
                             self._max_pages)
                self._count_keys(first, window)
        _mx.inc("serving.engine.page_steps", pages)

    def _count_keys(self, first: int, n: int) -> None:
        """`n` queries at positions first .. first + n - 1 of one sequence:
        `context_keys` adds the keys each of them sees (its position + 1),
        `selected_keys` those its attention weighs: all of them, or the
        `index_topk` a latent model's indexer selects. Arithmetic on
        positions, the model's own: the latent kernel still WALKS every
        live row under the selection's mask (`page_steps` counts the walk),
        so their ratio is the share of the context that enters the softmax,
        not the share of it that is read."""
        seen = n * first + n * (n + 1) // 2
        if self._block:
            # a query sees its block to the end: j // B <= i // B
            at = np.arange(first, first + n)
            seen = int(((at // self._block + 1) * self._block).sum())
        _mx.inc("serving.engine.context_keys", seen)
        if self._topk is None or first + n <= self._topk:
            _mx.inc("serving.engine.selected_keys", seen)
            return
        full = max(0, min(n, self._topk - first))   # queries that see <= topk
        _mx.inc("serving.engine.selected_keys",
                full * first + full * (full + 1) // 2
                + (n - full) * self._topk)

    def _deliver(self, slot: int, tok: int, first: bool,
                 note: Optional[tuple] = None) -> bool:
        """Push one token to the slot's ticket; True when it was the
        request's last (the slot and its pages are released then)."""
        st = self._slots[slot]  # graftlint: disable=lock-discipline (engine-thread owned; see ownership note above _next_tick)
        if st is None:
            # a frame for a slot the host already retired would mean the
            # device/host retirement conditions diverged — loud beats wrong
            log.warning("engine: token for free slot %d dropped", slot)
            return True
        st.out.append(tok)
        _mx.inc("serving.tokens_total")
        now = time.perf_counter()
        if first:
            st.t_first = now
            st.req.ticket.t_first = now
            _mx.observe("serving.ttft", now - st.req.ticket.t_submit)
        # push BEFORE the done decision: a stream() consumer sees every
        # token, including the one that retires the slot
        st.req.ticket._push(tok, note)
        done = (tok == self._eos) or (len(st.out) >= st.req.max_new)
        if done:
            # avg time-between-tokens over the request's decode phase (the
            # chunked fetch makes per-token host deltas bursty; the
            # request-level mean is the honest figure)
            if len(st.out) > 1 and st.t_first is not None:
                _mx.observe("serving.tbt",
                            (now - st.t_first) / (len(st.out) - 1))
            st.req.ticket.t_done = now
            # release BEFORE the done event: a waiter returning from
            # result() (the diagnosis probe, capacity tests) must
            # observe the pool already reclaimed — releasing after
            # set() leaves a window where free+resident < budget
            self._release_slot_pages(st)
            st.req.ticket._finish()
            with self._cond:
                self._slots[slot] = None
                # a stop() may have reset the free list already — don't
                # re-add the slot on top of the reset
                if not self._stopping:
                    self._free.append(slot)
                self._cond.notify_all()
            _mx.inc("serving.engine.completions")
        return done

    def _fail_outstanding(self, err: BaseException) -> None:
        with self._cond:
            reqs = list(self._waiting)
            self._waiting.clear()
            slots = [s for s in self._slots if s is not None]
            self._slots = [None] * self.n_slots
            self._free = list(range(self.n_slots))
            swap, self._pending_swap = self._pending_swap, None
        if swap is not None:
            # release the waiting swapper with the failure, not a timeout
            swap.error = err
            swap.applied.set()
        # the device cache is garbage after a crash — every page and
        # every cached prefix goes with it
        self._admissions.clear()
        self._free_pages = list(range(1, self._n_pages))
        self._prefix.clear()
        _mx.set_gauge("serving.kv_pages_free", len(self._free_pages))
        # last-value-wins gauges would otherwise report the pre-crash
        # depth/occupancy forever
        _mx.set_gauge("serving.engine.queue", 0)
        _mx.set_gauge("serving.slots_active", 0)
        for r in reqs:
            r.ticket._finish(err)
        for s in slots:
            s.req.ticket._finish(err)
