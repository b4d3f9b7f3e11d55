"""Predictors: the servable model contract + JAX implementations.

(reference: serving/fedml_predictor.py:10 — FedMLPredictor ABC with one
`predict(input_json)` method; user subclasses wrap their model.)

TPU-first details in JaxPredictor:
- the forward pass is jitted ONCE per batch bucket: inputs are padded up to
  the nearest power-of-two batch so arbitrary request sizes reuse a handful
  of compiled programs instead of recompiling per shape (XLA static-shape
  rule; SURVEY §7 design stance).
- bf16 compute via models/hub.mixed_precision_apply composes here too —
  pass the wrapped apply_fn.

GreedyLMPredictor serves the FedLLM slice (llm/TransformerLM + merged LoRA):
greedy argmax decoding as ONE jitted lax.scan over decode steps (bucketed
step counts), so a request costs one device dispatch instead of one per
token — a host round trip per token otherwise sits between every two
decode steps. kv_cache=True additionally swaps the per-step full-buffer
recompute for the KV-cached functional decode (llm/decode.py), identical
tokens (parity-pinned in tests/test_kv_decode.py).
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics as _mx
from ..utils.events import recorder

Pytree = Any


class InvalidRequest(ValueError):
    """Client-side request error. The HTTP layer maps this (plus missing-
    field KeyErrors) to 400; every OTHER exception is a 500. The split
    matters twice over at the gateway: a 4xx must never kill a healthy
    replica (hostile input can't take replicas out of rotation), and a
    genuine internal failure must be a 5xx so failover actually happens —
    classifying by builtin ValueError/TypeError would misfile internal
    JAX shape/dtype errors as client errors."""


class StaleVersion(InvalidRequest):
    """The request PINNED a `model_version` this replica does not serve
    (rolling update in progress). The HTTP layer maps this to 409 — its
    own code because the gateway's contract differs from both 4xx and
    5xx: the replica is healthy (never marked dead) but the request
    should be RETRIED on a sibling replica that already (or still)
    serves the pinned version."""


def _req_int(input_json: dict, key: str, default) -> int:
    try:
        return int(input_json.get(key, default))
    except (TypeError, ValueError):
        raise InvalidRequest(
            f"{key} must be an integer; got {input_json.get(key)!r}"
        ) from None


class Predictor(Protocol):
    """reference: serving/fedml_predictor.py FedMLPredictor.predict."""

    def predict(self, input_json: dict) -> Any: ...


class _InstrumentedPredictor:
    """Telemetry shim shared by the JAX predictors (ISSUE 2): `predict`
    wraps the subclass's `_predict(input_json) -> (out, compile_key)` in a
    `serving.predict` span and separates compile-vs-serve time — the first
    call for a given compile key (bucket signature → one XLA program) lands
    in the `serving.predict.compile_s` histogram, warm calls in
    `serving.predict.serve_s`. The split is what makes a cold p99 legible:
    a 2 s first-bucket compile and a 2 ms steady serve must not share a
    histogram."""

    def predict(self, input_json: dict) -> dict:
        compiled = self.__dict__.setdefault("_compiled_keys", set())
        t0 = time.perf_counter()
        with recorder.span("serving.predict",
                           kind=type(self).__name__) as sp:
            out, key = self._predict(input_json)
            first = key not in compiled
            sp.meta["compile"] = first
        # the program compiled whether or not the pin below 409s —
        # record it first, or the next same-shape request would land its
        # serve-time latency in the compile histogram
        compiled.add(key)
        # pin is re-checked AFTER compute: a hot swap that lands while
        # this request decodes makes the engine finish in-flight slots on
        # the NEW adapters — returning that output under an old-version
        # pin would be the spliced mixed-version answer pinning exists to
        # prevent. The 409 reroutes to a sibling (decode cost is the
        # price of the read-your-round contract).
        chk = getattr(self, "_check_pin", None)
        if chk is not None:
            chk(input_json)
        _mx.inc("serving.predictions")
        _mx.observe("serving.predict.compile_s" if first
                    else "serving.predict.serve_s",
                    time.perf_counter() - t0)
        return out


def lm_predictor_from_serve_knobs(sv: dict, model, params,
                                  adapters=None, detokenize=None,
                                  default_max_len: int = 256
                                  ) -> "GreedyLMPredictor":
    """THE serve-knob -> GreedyLMPredictor mapping for every knob
    serving/knobs.py tags `consumer: predictor` (the registry is the one
    authoritative key list; graftlint's knob-drift rule fails the build
    if this function and the registry disagree). Shared by the config
    route (serving.lm_predictor_from_config reads
    Config.serve_args.extra) and the deploy route
    (scheduler.start_replica reads the spec's serve dict) — one mapping,
    so the two surfaces cannot drift."""
    eos = sv.get("engine_eos_id")
    page, n_pages = sv.get("kv_page_size"), sv.get("kv_n_pages")
    return GreedyLMPredictor(
        model, params, adapters=adapters, detokenize=detokenize,
        max_len=int(sv.get("engine_max_len", default_max_len)),
        kv_cache=bool(sv.get("kv_cache", True)),
        decode_slots=int(sv.get("decode_slots", 0)),
        eos_id=None if eos is None else int(eos),
        engine_fetch_chunk=int(sv.get("engine_fetch_chunk", 2)),
        sampler_cache_size=int(sv.get("sampler_cache_size", 4)),
        engine_mp=int(sv.get("engine_mp", 0)),
        kv_page_size=None if page is None else int(page),
        kv_n_pages=None if n_pages is None else int(n_pages),
        prefill_chunk=int(sv.get("prefill_chunk", 0)),
        prefix_cache=bool(sv.get("prefix_cache", True)),
        paged_kernel=bool(sv.get("paged_kernel", False)),
        # a YAML-1.1 deploy spec reads unquoted `off` as False — the
        # documented disable spelling; normalize like config.validate
        spec_decode=("off" if sv.get("spec_decode") in (None, False)
                     else str(sv.get("spec_decode"))),
        spec_k=int(sv.get("spec_k", 4)),
        # same YAML-1.1 normalization: unquoted `off` parses as False
        kv_quant=("off" if sv.get("kv_quant") in (None, False)
                  else str(sv.get("kv_quant"))),
        admit_batch=int(sv.get("admit_batch", 1)),
        drain_timeout_s=float(sv.get("drain_timeout_s", 30.0)))


def _bucket(n: int, pow2_cap: int = 1024) -> int:
    """Power-of-two buckets up to the cap, then multiples of the cap — every
    batch size maps to a bounded set of compiled programs."""
    if n > pow2_cap:
        return ((n + pow2_cap - 1) // pow2_cap) * pow2_cap
    b = 1
    while b < n:
        b *= 2
    return b


class JaxPredictor(_InstrumentedPredictor):
    """Classification predictor over (apply_fn, params).

    predict({"inputs": [[...], ...]}) -> {"predictions": [...],
    "probabilities": [[...], ...]} — batch padded to a power-of-two bucket,
    one jitted program per bucket."""

    def __init__(self, apply_fn: Callable, params: Pytree,
                 return_probs: bool = True):
        self.params = params
        self.return_probs = return_probs

        @jax.jit
        def fwd(params, x):
            logits = apply_fn({"params": params}, x)
            return jnp.argmax(logits, -1), jax.nn.softmax(logits, -1)

        self._fwd = fwd

    def _predict(self, input_json: dict) -> tuple[dict, tuple]:
        try:
            x = np.asarray(input_json["inputs"], np.float32)
        except (TypeError, ValueError):
            raise InvalidRequest(
                "inputs must be a rectangular numeric array") from None
        n = x.shape[0]
        b = _bucket(n)
        if b > n:
            x = np.concatenate([x, np.zeros((b - n,) + x.shape[1:], x.dtype)])
        labels, probs = self._fwd(self.params, jnp.asarray(x))
        out = {"predictions": np.asarray(labels)[:n].tolist()}
        if self.return_probs:
            out["probabilities"] = np.asarray(probs)[:n].round(6).tolist()
        return out, (b, x.shape[1:])


class GreedyLMPredictor(_InstrumentedPredictor):
    """Causal-LM predictor for llm/TransformerLM (optionally with LoRA
    merged via llm.lora.lora_merge before construction).

    predict({"tokens": [...], "max_new_tokens": k}) ->
    {"generated_tokens": [...], "generated_text": "..."} (text only when a
    detokenizer fn is supplied).

    The WHOLE generation is one jitted program: a lax.scan over decode
    steps on a fixed-size token buffer, with the step count bucketed to
    powers of two (one compiled program per bucket). The naive alternative
    — one jit call per token — costs a host↔device round trip per token;
    the scanned form dispatches once per REQUEST.

    kv_cache=True (default-dense-attention models only) replaces the
    per-step full-buffer recompute with the KV-cached functional decode
    (llm/decode.py): O(D² + T·D) per token instead of O(T·D²), computed
    in the params' own dtype so numerics match the recompute path (same
    tokens; parity-pinned). Prompts are bucketed and the real length
    rides traced, so the compile cache stays bounded on both paths.

    decode_slots=S (requires kv_cache=True) additionally starts the
    continuous-batching DecodeEngine (serving/engine.py): S slots share
    one persistent donated pool of KV pages and concurrent requests
    decode in the SAME device steps instead of serializing —
    single-prompt requests without top_k route there (greedy output
    token-identical to the per-request path); batched and top_k requests
    keep the per-request path. stop() shuts the engine down.

    The engine's knobs all need decode_slots: kv_page_size=P is the page
    (None = the engine's default, 16), kv_n_pages sizes the pool,
    prefill_chunk enables chunked-prefill admission, prefix_cache reuses
    identical prompt-prefix pages (engine module docstring has the full
    story); engine capacity is the page budget, consulted through
    engine.admissible() so routing and the 400/degrade contracts follow
    the real constraint.

    paged_kernel=True / spec_decode="ngram" (+ spec_k) turn on the
    engine's decode-speed legs (serving/engine.py: fused Pallas
    paged attention; greedy-exact self-drafted speculation). Neither
    changes routing or the degrade contract: both are token-identical
    to the plain engine — speculation keeps the engine's per-position
    rng schedule, so even seeded sampling degrades/surfaces exactly as
    before (the per-request path's schedule is the one that differs,
    which _must_surface_engine_failure already covers)."""

    def __init__(self, model, params: Pytree,
                 detokenize: Optional[Callable[[list[int]], str]] = None,
                 max_len: int = 256, kv_cache: bool = False,
                 adapters: Optional[Pytree] = None,
                 compute_dtype: Optional[str] = None,
                 decode_slots: int = 0, eos_id: Optional[int] = None,
                 sampler_cache_size: int = 4, engine_fetch_chunk: int = 2,
                 engine_mp: int = 0, kv_page_size: Optional[int] = None,
                 kv_n_pages: Optional[int] = None, prefill_chunk: int = 0,
                 prefix_cache: bool = True, paged_kernel: bool = False,
                 spec_decode: str = "off", spec_k: int = 4,
                 kv_quant: str = "off", admit_batch: int = 1,
                 drain_timeout_s: float = 30.0):
        from ..llm.decode import engine_only, require_servable

        require_servable(model)
        # what only the engine's programs run (latent attention, grouped
        # heads, q/k norms, experts, a diffusion block: llm/decode.py
        # `engine_only`): no per-request path exists to degrade to, or to
        # take batched rows and top_k
        why = engine_only(model)
        self._engine_only = bool(why)
        if self._engine_only and not decode_slots:
            raise NotImplementedError(
                f"{why}: served by the decode engine only "
                "(serve decode_slots > 0): the per-request programs "
                "(llm/decode.py make_kv_decode) are the dense block's")
        self.model = model
        self.params = params
        self.detokenize = detokenize
        self.max_len = max_len
        self.kv_cache = kv_cache
        self.adapters = adapters
        self.engine = None
        self.eos_id = eos_id
        self.drain_timeout_s = float(drain_timeout_s)
        self._version = 0

        if decode_slots and not kv_cache:
            raise ValueError(
                "decode_slots (the continuous-batching engine, "
                "serving/engine.py) needs kv_cache=True — the engine IS "
                "the KV-cached decode with a slot axis")
        named = [knob for knob, on in (
            ("kv_page_size", kv_page_size is not None),
            ("kv_n_pages", kv_n_pages), ("prefill_chunk", prefill_chunk),
            ("paged_kernel", paged_kernel),
            ("spec_decode", spec_decode != "off"),
            ("kv_quant", kv_quant != "off"),
            ("admit_batch", int(admit_batch) > 1)) if on]
        if named and not decode_slots:
            raise ValueError(
                f"{'/'.join(named)} configure the decode ENGINE "
                "(serving/engine.py) — they need decode_slots > 0 "
                "(otherwise they would be silently ignored)")

        if adapters is not None and not kv_cache:
            # the recompute path drives model.apply, which knows nothing of
            # adapter trees or int8 {q,s} leaves; the kv decode handles both
            raise ValueError(
                "adapters (the QLoRA serving layout: frozen base + LoRA) "
                "need kv_cache=True — the functional decode merges them "
                "per layer; or pre-merge with llm.lora.lora_merge and pass "
                "plain params")
        if compute_dtype is not None and not kv_cache:
            raise ValueError(
                "compute_dtype only applies to kv_cache=True (the "
                "recompute path runs model.apply in the params' own "
                "dtype); cast the params instead, e.g. "
                "jax.tree.map(lambda a: a.astype(dtype), params)")
        if kv_cache:
            # O(D² + T·D) per token via llm/decode.py instead of a full
            # O(T·D²) recompute — parity-pinned in tests/test_kv_decode.py.
            # Needs the model's own dense attention (a custom attn_fn is
            # not replicated by the functional decode body).
            if model.attn_fn is not None:
                raise ValueError(
                    "kv_cache=True supports the default dense attention "
                    "only (custom attn_fn is not replicated by the "
                    "functional decode body)")
            from ..llm.decode import (
                make_greedy_generate, stack_adapter_blocks, stack_blocks,
            )

            # unrolled-layout adapters restack alongside the params —
            # block_i/... keys would otherwise be silently ignored by
            # split_adapters' blocks/ routing
            self.adapters = stack_adapter_blocks(adapters, model.n_layers)
            # the kv path never touches the unrolled tree again — keep ONE
            # copy resident (stack_blocks materializes a full stacked copy
            # for unrolled inputs; holding both would double parameter
            # HBM), and self.params IS the tree the kv path serves
            self.params = stack_blocks(params, model.n_layers)
            # decode in the params' own compute dtype, so kv and recompute
            # paths see the same numerics (float params stay float32; a
            # bf16-cast tree decodes in bf16, matching model.apply).
            # compute_dtype overrides — e.g. "bfloat16" for an int8 base
            # whose float leaves are the f32 scales
            if compute_dtype is not None:
                kv_dtype = jnp.dtype(compute_dtype)
            else:
                float_leaves = [l for l in jax.tree.leaves(self.params)
                                if jnp.issubdtype(l.dtype, jnp.floating)]
                kv_dtype = (float_leaves[0].dtype if float_leaves
                            else jnp.float32)
            kv_gen = make_greedy_generate(model.n_heads, dtype=kv_dtype)

            # prompts are right-padded to a power-of-two bucket and the
            # real length rides as a traced arg, so compiled programs are
            # keyed by (prompt bucket, step bucket) — bounded, like the
            # recompute path's fixed buffer
            @functools.partial(jax.jit, static_argnums=(4, 5))
            def generate_kv(params, adapters, tokens, length, max_len,
                            n_steps):
                return kv_gen(params, adapters, tokens, max_len, n_steps,
                              length=length)

            self._generate_kv = generate_kv
            self._kv_dtype = kv_dtype
            # top_k -> jitted sampling generate, LRU-BOUNDED: a hostile or
            # merely diverse stream of top_k values would otherwise grow
            # one jitted wrapper (and its compile cache) per bucket without
            # limit. Evicting the oldest drops its XLA executables with it;
            # evictions are counted so a thrashing cache is visible.
            self._samplers: "OrderedDict[int, Any]" = OrderedDict()
            self._samplers_cap = max(1, int(sampler_cache_size))
            # FedMLInferenceRunner serves via ThreadingHTTPServer, so two
            # first requests for the same top_k bucket can race here; without
            # the lock each would build + jit its own generate wrapper — a
            # duplicate multi-minute XLA compile at large model scale
            self._samplers_lock = threading.Lock()
            if decode_slots:
                # continuous batching (serving/engine.py): S slots share
                # one persistent donated KV page pool; requests stream
                # through the engine thread instead of serializing on this
                # predictor's jit calls. engine_mp > 1 runs the engine
                # tensor-parallel over an {"mp": N} device mesh (weights +
                # KV pool sharded via the parallel/partition.py registry).
                from .engine import DecodeEngine

                mesh = None
                if int(engine_mp) > 1:
                    from ..parallel.mesh import make_mesh

                    mesh = make_mesh({"mp": int(engine_mp)})
                self.engine = DecodeEngine(
                    model, self.params, adapters=self.adapters,
                    n_slots=int(decode_slots), max_len=max_len,
                    eos_id=eos_id, dtype=kv_dtype,
                    fetch_chunk=engine_fetch_chunk, mesh=mesh,
                    **({} if kv_page_size is None
                       else {"page_size": int(kv_page_size)}),
                    n_pages=kv_n_pages,
                    prefill_chunk=prefill_chunk,
                    prefix_cache=prefix_cache,
                    paged_kernel=paged_kernel, spec_decode=spec_decode,
                    spec_k=spec_k, kv_quant=kv_quant,
                    admit_batch=int(admit_batch)).start()
                # the per-request path serves the engine's laid-out tree:
                # one resident copy of the weights
                self.params = self.engine.params
            return

        # n_steps is a Python int at trace time (scan length must be
        # static) -> one compiled program per power-of-two bucket
        @functools.partial(jax.jit, static_argnums=(3,))
        def generate(params, buf, length, n_steps):
            def step(carry, _):
                buf, pos = carry
                logits = model.apply({"params": params}, buf)
                nxt = jnp.argmax(logits[0, pos - 1]).astype(jnp.int32)
                buf = jax.lax.dynamic_update_slice(
                    buf, nxt[None, None], (0, pos))
                return (buf, pos + 1), nxt

            (_buf, _pos), toks = jax.lax.scan(
                step, (buf, length), None, length=n_steps)
            return toks

        self._generate = generate

    def stop(self, drain: bool = False) -> None:
        """Shut down the continuous-batching engine, if one was started.
        `drain=True` lets in-flight engine requests finish first, bounded
        by this predictor's `drain_timeout_s` — the runner's stop() path
        uses it so a scale-down or rolling replica replacement never
        kills a request that was already decoding."""
        if self.engine is not None:
            self.engine.stop(drain=drain,
                             drain_timeout_s=self.drain_timeout_s)

    # ------------------------------------------------------ fleet surface
    @property
    def model_version(self) -> int:
        """The adapter version this replica serves (monotonic; bumped by
        swap_adapters). The engine's counter when one runs — the
        per-request degrade path swaps in lockstep, so the version is
        honest on both paths."""
        return (self.engine.model_version if self.engine is not None
                else self._version)

    def swap_adapters(self, adapters: Pytree,
                      version: Optional[int] = None) -> int:
        """Hot-swap the LoRA adapter values this predictor serves — the
        rolling-update primitive (serving/engine.py swap_adapters has the
        atomicity story). The per-request fallback path swaps in the SAME
        call, so an engine that later dies degrades to a path serving the
        same version, not stale weights. Returns the new model_version."""
        if not self.kv_cache:
            raise ValueError(
                "adapter hot swap needs kv_cache=True — the recompute "
                "path serves pre-merged params (llm.lora.lora_merge); "
                "redeploy the replica instead")
        if self.adapters is None:
            raise ValueError(
                "this predictor was built without adapters — hot swap "
                "replaces adapter VALUES only; deploy with adapters "
                "(zero-initialized LoRA serves the base model exactly)")
        if self.engine is not None:
            ver = self.engine.swap_adapters(adapters, version=version)
            # the degrade path must serve the same weights the engine does
            self.adapters = self.engine.adapters
            self._version = ver
            return ver
        from .engine import prepare_adapter_swap

        stacked, ver = prepare_adapter_swap(
            self.adapters, adapters, self.model.n_layers,
            self._version, version, who="this replica")
        with recorder.span("serving.swap", version=ver):
            self.adapters = stacked
            self._version = ver
        _mx.set_gauge("serving.model_version", ver)
        # the serving tier's ONE swap counter (top's fleet line reads
        # it): engine-backed and degraded-path swaps both count
        _mx.inc("serving.engine.swaps")
        return ver

    def _check_pin(self, input_json: dict) -> None:
        """Per-request version pinning: a request naming `model_version`
        is answered ONLY by a replica serving exactly that version — the
        contract that lets the gateway keep a mixed-version fleet honest
        mid-rolling-update (a 409 reroutes to a sibling; it never kills
        the replica)."""
        pin = input_json.get("model_version")
        if pin is None:
            return
        try:
            pin = int(pin)
        except (TypeError, ValueError):
            raise InvalidRequest(
                f"model_version must be an integer; got {pin!r}") from None
        if pin != self.model_version:
            raise StaleVersion(
                f"request pinned model_version {pin}; this replica "
                f"serves {self.model_version}")

    def _parse_request(self, input_json: dict, batched: bool
                       ) -> tuple[list, float, list, int]:
        """The validation contract /predict and its streaming form MUST
        share (one helper so the two paths can't drift): integer tokens,
        numeric sampling knobs, non-empty rows, sampling-needs-kv_cache,
        and knob/temperature consistency. Returns (rows, temperature,
        knobs, max_new_tokens)."""
        raw = input_json["tokens"]
        try:
            rows = [[int(t) for t in r]
                    for r in (raw if batched else [raw])]
            temperature = float(input_json.get("temperature", 0.0))
            knobs = [k for k in ("top_k", "seed")
                     if int(input_json.get(k) or 0) != 0]
        except (TypeError, ValueError):
            raise InvalidRequest(
                "tokens must be integers and temperature/top_k/seed "
                "numeric") from None
        if not rows or any(not r for r in rows):
            raise InvalidRequest(
                "tokens must contain at least one prompt token"
                " (per row, for a batch)")
        # a knob at its documented disabled default (top_k=0, seed=0) is
        # equivalent to omitting it — client SDKs that serialize defaults
        # must not be rejected on greedy requests
        if (temperature > 0 or knobs) and not self.kv_cache:
            raise InvalidRequest(
                "sampling (temperature/top_k/seed) needs kv_cache=True; "
                "the recompute path is greedy-only")
        if temperature <= 0 and knobs:
            raise InvalidRequest(
                f"{'/'.join(knobs)} only apply when temperature > 0 "
                "(temperature omitted or 0 means greedy decoding — the "
                "knobs would be silently ignored)")
        return (rows, temperature, knobs,
                _req_int(input_json, "max_new_tokens", 16))

    def _denoising(self, input_json: dict) -> dict:
        """The request's two block-diffusion parameters as `engine.submit`
        takes them: `denoising_steps` (default: the model's block length)
        and `confidence_threshold` (default 0.9; null: the static rule).
        A model that generates a token a step refuses both with a
        sentence (engine.denoising), as does a predictor without an
        engine."""
        named = [k for k in ("denoising_steps", "confidence_threshold")
                 if k in input_json]
        if self.engine is None or not self.engine.model.diffusion_block:
            if named:
                raise InvalidRequest(
                    f"{'/'.join(named)}: a block-diffusion model's "
                    "parameters; this model generates one token a step")
            return {}
        from .engine import CONFIDENCE_THRESHOLD

        steps, threshold = self.engine.denoising(
            input_json.get("denoising_steps"),
            input_json.get("confidence_threshold", CONFIDENCE_THRESHOLD))
        return {"denoising_steps": steps, "confidence_threshold": threshold}

    def _must_surface_engine_failure(self, prompt_len: int, new: int,
                                     temperature: float,
                                     seed: Optional[int]) -> bool:
        """Degrade contract, shared by both paths: True when the
        per-request fallback could NOT honor what the engine promised, so
        an engine failure must surface (500 -> gateway failover) instead
        of silently degrading:
        - seeded sampling: the per-request rng schedule differs, same
          seed would return different tokens with no signal
        - engine_eos_id: the per-request path has no eos support,
          degraded output would include post-eos tokens
        - engine-only capacity: prompt + bucket(max_new) over max_len
          would turn a previously-valid request into a permanent,
          misleading 400"""
        return (self._engine_only
                or (temperature > 0 and seed is not None)
                or self.eos_id is not None
                or prompt_len + _bucket(max(new, 1), pow2_cap=self.max_len)
                > self.max_len)

    def _predict(self, input_json: dict) -> tuple[dict, tuple]:
        self._check_pin(input_json)
        raw = input_json["tokens"]
        # {"tokens": [[...], [...]]} = a BATCH of prompts decoded in
        # lockstep through one program (kv_cache only; rows may differ in
        # length); {"tokens": [...]} = one prompt
        batched = bool(raw) and isinstance(raw[0], (list, tuple))
        rows, temperature, knobs, new = self._parse_request(
            input_json, batched)
        denoising = self._denoising(input_json)
        if batched and not self.kv_cache:
            raise InvalidRequest(
                "batched prompts need kv_cache=True (the recompute path "
                "decodes one prompt per program)")
        toks = max(rows, key=len)     # longest row drives capacity checks
        # continuous-batching route (serving/engine.py): single prompts
        # without a top_k cutoff stream through the slot engine — the
        # request blocks on its ticket while OTHER requests decode in the
        # same device steps. Batched rows (already one program) and top_k
        # requests (need a static-k compiled cutoff) stay on the
        # per-request path. Capacity rides the ENGINE's oracle
        # (engine.admissible — exact prompt + max_new <= max_len, plus
        # the page budget), not static max_len math: a
        # request the page budget refuses falls through to the
        # per-request path below when that path can serve it honestly,
        # instead of 400ing a request this replica could answer. Routing
        # is deterministic per (prompt_len, max_new) — admissible() is
        # budget math, not current occupancy — so a given request shape
        # always takes the same path (seeded sampling stays reproducible).
        if (self.engine is not None and not batched
                and int(input_json.get("top_k", 0) or 0) == 0
                and not self.engine.admissible(len(rows[0]), max(new, 1))):
            if self.eos_id is not None or len(rows[0]) + _bucket(
                    max(new, 1), pow2_cap=self.max_len) > self.max_len:
                # neither path can serve this honestly (the per-request
                # path has no eos support / its bucketed capacity is also
                # exceeded) — surface the ENGINE's contract, page math
                # included, rather than the per-request message
                raise InvalidRequest(
                    self.engine.capacity_error(len(rows[0]), max(new, 1)))
        elif (self.engine is not None and not batched
                and int(input_json.get("top_k", 0) or 0) == 0):
            seed = int(input_json["seed"]) if "seed" in input_json else None
            gen = None
            try:
                # engine stopped/died (at submit, or mid-flight after
                # admission — the crash handler errors live tickets):
                # degrade to the per-request path below instead of erroring
                # the request — the replica keeps serving, just without
                # batching. A ticket TIMEOUT is not degraded: 600s have
                # already passed, re-decoding would double it.
                gen = self.engine.submit(
                    rows[0], max(new, 1), temperature=temperature,
                    seed=seed, **denoising).result(timeout=600.0)[:new]
            except RuntimeError:
                # Degrade ONLY when the per-request path honors the same
                # contract the engine did; otherwise surface the failure
                # (a 500; the gateway fails the replica over) — the
                # shared _must_surface_engine_failure predicate
                if self._must_surface_engine_failure(
                        len(rows[0]), new, temperature, seed):
                    raise
            if gen is not None:
                out = {"generated_tokens": gen}
                if self.detokenize is not None:
                    out["generated_text"] = self.detokenize(gen)
                return out, ("engine",
                             min(_bucket(len(toks), pow2_cap=self.max_len),
                                 self.max_len))
        # fixed-size buffer + bucketed step count => a BOUNDED set of
        # compiled programs (log2(max_len) step buckets). The capacity
        # contract is prompt + bucket(max_new_tokens) <= max_len — clamping
        # the bucket to the remaining space instead would mint one static
        # scan length (= one fresh XLA compile) per distinct prompt length
        # near the buffer edge.
        if self._engine_only:
            raise InvalidRequest(
                "this model is served by the decode engine only: one "
                "prompt a request, no top_k, within the engine's capacity ("
                + self.engine.capacity_error(len(toks), max(new, 1)) + ")")
        steps = _bucket(max(new, 1), pow2_cap=self.max_len)
        if len(toks) + steps > self.max_len:
            raise InvalidRequest(
                f"prompt {len(toks)} + max_new_tokens {new} (bucketed to "
                f"{steps} decode steps) exceeds max_len {self.max_len}; "
                "shorten the prompt, lower max_new_tokens, or raise "
                "max_len")
        if self.kv_cache:
            pbucket = min(_bucket(len(toks), pow2_cap=self.max_len),
                          self.max_len)
            # the row count is ALSO bucketed (dummy rows repeat row 0,
            # sliced off below): batch sizes 3 and 4 share one compiled
            # program instead of each minting a fresh prefill+scan compile
            n_rows = len(rows)
            bbucket = _bucket(n_rows) if batched else 1
            prompt = np.zeros((bbucket, pbucket), np.int32)
            row_lens = []
            for i in range(bbucket):
                r = rows[i] if i < n_rows else rows[0]
                prompt[i, : len(r)] = r
                row_lens.append(len(r))
            lengths = (jnp.asarray(row_lens, jnp.int32) if batched
                       else jnp.int32(len(toks)))
            if temperature > 0:
                # sampling: softmax(logits/T) with optional static top-k —
                # T and the seed ride traced (the HF generate() knobs the
                # reference's serving surface inherits). top_k is a
                # compile-time shape knob, so it is VALIDATED and rounded
                # up to a power of two: the compile cache stays bounded at
                # log2(vocab) programs instead of one per raw client value
                top_k = int(input_json.get("top_k", 0))
                vocab = int(self.model.vocab_size)
                if top_k < 0 or top_k > vocab:
                    raise InvalidRequest(
                        f"top_k must be in [0, vocab_size={vocab}]; got "
                        f"{top_k} (0 disables the cutoff)")
                if top_k:
                    top_k = min(_bucket(top_k, pow2_cap=vocab), vocab)
                with self._samplers_lock:
                    gen = self._samplers.get(top_k)
                    if gen is not None:
                        self._samplers.move_to_end(top_k)  # LRU touch
                    else:
                        from ..llm.decode import make_generate

                        kv_gen = make_generate(self.model.n_heads,
                                               dtype=self._kv_dtype,
                                               sample=True, top_k=top_k)

                        @functools.partial(jax.jit, static_argnums=(4, 5))
                        def gen(params, adapters, tokens, length, max_len,
                                n_steps, rng, temp):
                            return kv_gen(params, adapters, tokens, max_len,
                                          n_steps, length=length, rng=rng,
                                          temperature=temp)

                        self._samplers[top_k] = gen
                        while len(self._samplers) > self._samplers_cap:
                            # evict coldest bucket — its jitted wrapper
                            # (and compiled programs) go with it; visible
                            # as a counter so thrash is diagnosable
                            self._samplers.popitem(last=False)
                            _mx.inc("serving.sampler_evictions")
                # no client seed -> a fresh one per request, so repeated
                # sampling requests VARY (the normal serving contract);
                # pass "seed" explicitly for reproducible generations
                if "seed" in input_json:
                    seed = int(input_json["seed"])
                else:
                    import random as _random

                    seed = _random.getrandbits(31)
                key = ("kv", pbucket, bbucket, steps, top_k)
                out_toks = gen(
                    self.params, self.adapters, jnp.asarray(prompt),
                    lengths, int(self.max_len), int(steps),
                    jax.random.key(seed), jnp.float32(temperature))
            else:
                key = ("kv", pbucket, bbucket, steps, -1)
                out_toks = self._generate_kv(
                    self.params, self.adapters, jnp.asarray(prompt),
                    lengths, int(self.max_len), int(steps))
        else:
            key = ("recompute", steps)
            buf = np.zeros((1, self.max_len), np.int32)
            buf[0, : len(toks)] = toks
            out_toks = self._generate(self.params, jnp.asarray(buf),
                                      jnp.int32(len(toks)), int(steps))
        arr = np.asarray(out_toks)
        if batched:
            # generate() returns 1-D for a single row; normalize, then
            # drop the bucket-padding dummy rows
            arr = np.atleast_2d(arr)[:n_rows]
            gen = arr[:, :new].tolist()
            out = {"generated_tokens": gen}
            if self.detokenize is not None:
                out["generated_text"] = [self.detokenize(g) for g in gen]
        else:
            gen = arr[:new].tolist()
            out = {"generated_tokens": gen}
            if self.detokenize is not None:
                out["generated_text"] = self.detokenize(gen)
        return out, key

    # ---------------------------------------------------------- streaming
    def predict_stream(self, input_json: dict):
        """Generator form of predict() for single-prompt requests: yields
        one {"token": t, "index": i} per generated token (a block-diffusion
        model's also carries "forward", the index within its block of the
        denoising forward that unmasked it, and "confidence"; several may
        arrive from one forward), then a final
        {"done": True, "generated_tokens": [...]} (plus generated_text
        with a detokenizer) — the payload the runner's SSE surface
        relays chunk by chunk.

        Engine-backed predictors stream LIVE: tokens surface as the
        engine's retirement frames land (granularity = fetch_chunk), so
        time-to-first-token is an engine iteration, not the whole
        request. Requests the engine can't take (top_k, page budget,
        dead engine within the degrade contract) compute through
        predict() in one program and then emit — degenerate timing,
        identical payload contract. Greedy streams are deterministic:
        re-running the same request yields the same token sequence,
        which is what lets the gateway re-serve a cut stream from token
        0 on a survivor replica."""
        self._check_pin(input_json)
        raw = input_json["tokens"]
        if raw and isinstance(raw[0], (list, tuple)):
            raise InvalidRequest(
                "streaming serves one prompt per request (batched rows "
                "return a single response; use /predict without stream)")
        rows_w, temperature, knobs, new = self._parse_request(
            input_json, batched=False)
        denoising = self._denoising(input_json)
        rows = rows_w[0]
        top_k = int(input_json.get("top_k", 0) or 0)
        pin = input_json.get("model_version")
        pin = int(pin) if pin is not None else None   # _check_pin validated
        ticket = None
        if (self.engine is not None and top_k == 0
                and self.engine.admissible(len(rows), max(new, 1))):
            seed = int(input_json["seed"]) if "seed" in input_json else None
            try:
                ticket = self.engine.submit(
                    rows, max(new, 1), temperature=temperature, seed=seed,
                    **denoising)
            except RuntimeError:
                # same degrade contract as predict(): greedy/unseeded
                # falls through to the one-shot path below
                if self._must_surface_engine_failure(
                        len(rows), new, temperature, seed):
                    raise
        if ticket is not None:
            out: list[int] = []
            for tok in ticket.stream(timeout=600.0):
                # a hot swap that lands mid-stream finishes this slot on
                # the NEW adapters — a pinned stream must fail (terminal
                # error event; the gateway reroutes/replays) rather than
                # silently splice model versions
                if pin is not None and self.model_version != pin:
                    raise StaleVersion(
                        f"request pinned model_version {pin}; this "
                        f"replica swapped to {self.model_version} "
                        "mid-stream")
                if len(out) >= new:
                    break       # new == 0: the engine still decoded one
                out.append(int(tok))
                chunk = {"token": int(tok), "index": len(out) - 1}
                note = ticket.note(len(out) - 1)
                if note is not None:
                    # a block-diffusion model's token: the forward of its
                    # block that unmasked it, and its confidence then
                    chunk["forward"], chunk["confidence"] = note
                yield chunk
            final = {"done": True, "generated_tokens": out}
            if self.detokenize is not None:
                final["generated_text"] = self.detokenize(out)
            yield final
            return
        res = self.predict(dict(input_json))
        gen = res["generated_tokens"]
        for i, t in enumerate(gen):
            yield {"token": int(t), "index": i}
        yield {"done": True, **res}
