"""Paged KV cache, prefix reuse, and chunked prefill (ISSUE 7).

The contracts the engine's page pool lives by:
- TOKEN IDENTITY: greedy output equals the per-request path's, and a
  seeded draw equals the documented one computed from the per-request
  path's logits, including mid-flight admission/retirement over shared
  prefix pages and on an mp=2 mesh; a default-constructed engine (pages
  of 16) holds the same across a page boundary;
- bounded programs: one paged step program + pow2 chunk buckets, no
  matter how many requests stream through;
- prefix-cache hygiene: refs released on retirement, no cross-request
  contamination after eviction, hashes keyed on token IDS not rendered
  text;
- chunked prefill actually interleaves: active decode slots make
  progress (and can finish) while a long prompt is mid-admission;
- capacity is the PAGE BUDGET: submit's 400 states the page math, and
  the predictor falls back to the per-request path for requests the
  budget refuses instead of wrongly 400ing them.

Jitted programs dominate this file's wall clock, so engines and the
per-request reference are MODULE-scoped and shared across tests (the
conftest still swaps a fresh metrics registry per test — counter
assertions below are deltas or per-test absolutes, both safe). Tests
that need a bespoke pool (eviction pressure, tiny budgets) construct
their own; capacity-only checks use UNSTARTED engines (submit validates
capacity before the started check, and construction never compiles).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine, _page_key
from fedml_tpu.serving.predictor import GreedyLMPredictor, InvalidRequest
from fedml_tpu.utils import metrics as _mx

V, D, L, H, FF = 96, 64, 2, 4, 128
MAXLEN = 32
PS = 4          # page size used throughout


@pytest.fixture(scope="module")
def setup():
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 10), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def per_req(setup):
    model, params = setup
    return GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True)


@pytest.fixture(scope="module")
def eng_paged(setup):
    """THE shared paged engine: 3 slots, 4-token pages, chunked prefill,
    prefix cache on, default (ample) pool."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4).start()
    yield eng
    eng.stop()


def _prompts(ns, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, V, n).tolist() for n in ns]


def _want(per_req, prompts, budgets):
    return [per_req.predict({"tokens": p, "max_new_tokens": b})
            ["generated_tokens"] for p, b in zip(prompts, budgets)]


# ----------------------------------------------------------- equivalence
def test_paged_greedy_token_identical_mid_flight_shared_pages(
        setup, per_req, eng_paged):
    """PINNED: 6 prompts — two sharing an 8-token prefix (shared pages +
    a prefix hit mid-run) — through 3 paged slots with chunked prefill,
    vs the per-request path. Admissions and retirements interleave
    mid-flight; every output must match token for token."""
    shared = _prompts((8,), seed=9)[0]
    prompts = _prompts((6, 10, 8, 5)) + [shared + p
                                         for p in _prompts((3, 5), seed=2)]
    budgets = [4, 7, 5, 6, 4, 5]
    want = _want(per_req, prompts, budgets)
    tickets = [eng_paged.submit(p, b) for p, b in zip(prompts, budgets)]
    assert [t.result(timeout=120) for t in tickets] == want


@pytest.fixture(scope="module")
def kv_oracle():
    """The per-request path's own prefill/step (llm/decode.py
    make_kv_decode), jitted once for the module."""
    from fedml_tpu.llm.decode import make_kv_decode

    prefill, step = make_kv_decode(H)
    return jax.jit(prefill, static_argnums=(3,)), jax.jit(step)


def _documented_draw(kv_oracle, params, prompt, n, temperature, seed):
    """The engine's documented draw, computed WITHOUT the engine: token i
    of a request is categorical(fold_in(key(seed), plen + i), logits /
    temperature) — the first off the prefill's last-position logits, each
    later one off the step that wrote position plen + i - 1 (so the key
    is fold_in(key(seed), pos + 1))."""
    prefill, step = kv_oracle
    key = jax.random.key(jnp.uint32(seed))
    plen = len(prompt)

    def draw(i, logits):
        return jax.random.categorical(
            jax.random.fold_in(key, plen + i),
            logits[0].astype(jnp.float32) / temperature)

    cache, logits = prefill(params, None, jnp.asarray([prompt]), MAXLEN)
    out = [draw(0, logits)]
    for i in range(1, n):
        cache, logits = step(params, None, cache, plen + i - 1,
                             out[-1][None].astype(jnp.int32))
        out.append(draw(i, logits))
    return [int(t) for t in out]


def test_paged_seeded_sampling_is_the_documented_draw(setup, kv_oracle,
                                                      eng_paged):
    """Sampling keeps an oracle that is not the engine: the paged engine
    draws the exact tokens the documented schedule gives over the
    per-request path's logits for the same (seed, temperature) — the rng
    schedule is independent of where K/V rows live — and the usual
    same-seed/diff-seed contract holds within the engine."""
    prompt = _prompts((8,), seed=11)[0]
    a = eng_paged.submit(prompt, 8, temperature=2.0, seed=7)
    b = eng_paged.submit(prompt, 8, temperature=2.0, seed=7)
    c = eng_paged.submit(prompt, 8, temperature=2.0, seed=8)
    a, b, c = (t.result(timeout=120) for t in (a, b, c))
    params = setup[1]
    assert a == _documented_draw(kv_oracle, params, prompt, 8, 2.0, 7)
    assert c == _documented_draw(kv_oracle, params, prompt, 8, 2.0, 8)
    assert a == b
    assert a != c


@pytest.fixture(scope="module")
def per_req_long(setup):
    """A per-request reference with room for a 32-step bucket."""
    model, params = setup
    return GreedyLMPredictor(model, params, max_len=64, kv_cache=True)


@pytest.mark.parametrize("case", [
    {"prompt": 15, "new": 8}, {"prompt": 16, "new": 8},
    {"prompt": 17, "new": 8},
    # the last page is a partial one: 40 = 2.5 pages of 16
    {"prompt": 20, "new": 20, "max_len": 40},
    {"serve": {"decode_slots": 2, "kv_n_pages": 8}},
    {"serve": {"decode_slots": 2, "paged_kernel": True}},
], ids=["prompt15", "prompt16", "prompt17", "max_len40", "serve_kv_n_pages",
        "serve_paged_kernel"])
def test_default_engine_runs_pages_of_16(setup, per_req_long, case):
    """An engine nobody gave a page size runs pages of 16 through the same
    programs as one that was: greedy tokens equal the per-request path's
    on both sides of a page boundary and into a partial last page, and a
    `serve` block that names a pool knob but no `kv_page_size` loads and
    serves from the engine."""
    model, params = setup
    if "serve" in case:
        from fedml_tpu.config import Config
        from fedml_tpu.serving import lm_predictor_from_config

        cfg = Config.from_dict(
            {"serve": {"engine_max_len": MAXLEN, **case["serve"]}})
        pred = lm_predictor_from_config(cfg, model, params)
        try:
            assert pred.engine.kv_page_size == 16
            req = {"tokens": _prompts((9,), seed=5)[0], "max_new_tokens": 6}
            done = _mx.snapshot()["counters"].get(
                "serving.engine.completions", 0)
            assert pred.predict(req) == per_req_long.predict(req)
            assert _mx.snapshot()["counters"][
                "serving.engine.completions"] == done + 1
        finally:
            pred.stop()
        return
    eng = DecodeEngine(model, params, n_slots=2,
                       max_len=case.get("max_len", MAXLEN)).start()
    try:
        assert eng.kv_page_size == 16
        prompt = _prompts((case["prompt"],), seed=3)[0]
        assert eng.submit(prompt, case["new"]).result(timeout=120) == \
            _want(per_req_long, [prompt], [case["new"]])[0]
    finally:
        eng.stop()


def test_paged_program_set_bounded_retrace_guard(eng_paged):
    """One paged step program; chunk programs bounded by pow2 buckets
    below prefill_chunk. A fresh wave over the warm engine (sampling on,
    new seeds/temps, prefix hits and misses) must not add a compile."""
    counts = eng_paged.program_counts()
    assert counts["step"] == 1, counts
    # chunks of 4 plus pow2 remainders {1, 2}: <= 3 programs ever
    assert counts["admit"] is None or counts["admit"] <= 3, counts
    for t in [eng_paged.submit(p, 4, temperature=1.3, seed=i)
              for i, p in enumerate(_prompts((6, 10, 3, 12), seed=4))]:
        t.result(timeout=120)
    assert eng_paged.program_counts() == counts, "retrace"


def test_paged_mp2_token_identical(setup, per_req):
    """Paged engine on an {"mp": 2} mesh (conftest forces 8 virtual CPU
    devices): weights Megatron-split, the page POOL sharded on its heads
    axis (partition.paged_kv_cache_spec), page table replicated — greedy
    output token-identical to the unmeshed paths (per-request pinned ==
    paged mp=1, the other link in the chain above)."""
    from fedml_tpu.parallel.mesh import make_mesh

    model, params = setup
    prompts = _prompts((6, 10, 8))
    want = _want(per_req, prompts, [5] * 3)
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4,
                       mesh=make_mesh({"mp": 2})).start()
    try:
        tickets = [eng.submit(p, 5) for p in prompts]
        assert [t.result(timeout=120) for t in tickets] == want
    finally:
        eng.stop()


# ----------------------------------------------------------- prefix cache
def test_prefix_refcount_release_on_retirement(eng_paged):
    """Full prompt pages register at admission (refs held by the slot),
    refs drop to zero at retirement while the entries STAY resident, and
    a resubmission hits them (counters + fewer chunks prefilled). All
    deltas — the engine is shared and warm."""
    prompt = _prompts((12,), seed=21)[0]     # 12 tokens = 3 full pages
    # the chain keys this prompt's pages register under, computed
    # independently of the engine (eviction churn from the shared
    # engine's history cannot fake these)
    keys, key = [], b"\x00"
    for i in range(3):
        key = _page_key(key, prompt[i * PS:(i + 1) * PS])
        keys.append(key)
    first = eng_paged.submit(prompt, 5).result(timeout=120)
    mine = [eng_paged._prefix[k] for k in keys]      # KeyError = not registered
    assert all(e.refs == 0 for e in mine)            # released on retirement
    # resident means NOT in the free pool (and not handed to anyone else)
    assert not {e.page for e in mine} & set(eng_paged._free_pages)
    snap0 = _mx.snapshot()["counters"]
    again = eng_paged.submit(prompt, 5).result(timeout=120)
    assert again == first
    snap = _mx.snapshot()["counters"]
    # hit capped at (12-1)//4 = 2 pages -> only the last page's worth of
    # prompt re-prefills (1 chunk of 4 vs 3 cold chunks)
    assert snap["serving.prefix_hits"] == snap0.get(
        "serving.prefix_hits", 0) + 1
    assert snap["serving.engine.prefill_chunks"] == \
        snap0["serving.engine.prefill_chunks"] + 1
    assert all(e.refs == 0 for e in eng_paged._prefix.values())


def test_prefix_hash_keyed_on_token_ids_not_text(per_req, eng_paged):
    """[12, 3] and [1, 23] render to the same digit string — a text-keyed
    hash would alias them. The chain key is over the int32 byte view."""
    assert _page_key(b"x", [12, 3]) != _page_key(b"x", [1, 23])
    tail = _prompts((6,), seed=3)[0]
    pa, pb = [12, 3, 7, 7] + tail, [1, 23, 7, 7] + tail
    want_b = per_req.predict({"tokens": pb, "max_new_tokens": 5})
    eng_paged.submit(pa, 5).result(timeout=120)
    misses0 = _mx.snapshot()["counters"]["serving.prefix_misses"]
    hits0 = _mx.snapshot()["counters"].get("serving.prefix_hits", 0)
    got_b = eng_paged.submit(pb, 5).result(timeout=120)
    # pb must MISS pa's entries (no alias) and decode correctly
    snap = _mx.snapshot()["counters"]
    assert snap["serving.prefix_misses"] == misses0 + 1
    assert snap.get("serving.prefix_hits", 0) == hits0
    assert got_b == want_b["generated_tokens"]


def test_prefix_eviction_no_cross_request_contamination(setup):
    """Fill a TINY pool with one prompt's resident prefix, force eviction
    via allocation pressure from different requests, then resubmit the
    first prompt: its pages were reused and overwritten by others, the
    map must not serve them — output equals the cold run exactly."""
    model, params = setup
    pa = _prompts((12,), seed=1)[0]
    # 6 usable pages; pa needs ceil((12+4)/4) = 4
    eng = DecodeEngine(model, params, n_slots=1, max_len=MAXLEN,
                       page_size=PS, n_pages=7, prefill_chunk=4).start()
    try:
        cold = eng.submit(pa, 4).result(timeout=120)
        assert len(eng._prefix) == 3
        # different prompts whose pages must come from evicting pa's
        for p in _prompts((12, 12), seed=2):
            eng.submit(p, 4).result(timeout=120)
        assert _mx.snapshot()["counters"].get(
            "serving.prefix_evictions", 0) > 0
        warm = eng.submit(pa, 4).result(timeout=120)
        assert warm == cold
    finally:
        eng.stop()


# -------------------------------------------------------- chunked prefill
def test_chunked_prefill_interleaves_with_decode(eng_paged):
    """An ACTIVE slot keeps decoding — and completes — while a long
    prompt admits chunk by chunk: the short request's completion lands
    strictly before the long request's first token. (With monolithic
    admission the engine loop admits the whole prompt before any further
    step dispatch.)"""
    short = _prompts((6,), seed=31)[0]
    long_p = _prompts((24,), seed=5)[0]
    ta = eng_paged.submit(short, 4)
    # wait until the short request is ACTIVE (first token delivered)
    deadline = time.monotonic() + 60
    while ta.t_first is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert ta.t_first is not None
    chunks0 = _mx.snapshot()["counters"]["serving.engine.prefill_chunks"]
    tb = eng_paged.submit(long_p, 4)
    a_out = ta.result(timeout=120)
    b_out = tb.result(timeout=120)
    assert len(a_out) == 4 and len(b_out) == 4
    # 24-token prompt, chunk 4 -> 6 chunk programs
    assert _mx.snapshot()["counters"][
        "serving.engine.prefill_chunks"] == chunks0 + 6
    # the short request finished while the long one was still admitting:
    # its completion precedes the long one's FIRST token
    assert ta.t_done < tb.t_first, (ta.t_done, tb.t_first)


# ------------------------------------------------- capacity + page budget
def test_paged_capacity_contract_and_page_math_message(setup):
    """admissible()/capacity_error() and submit's capacity 400 need no
    started engine (validation precedes the started check) and no
    compile (jits are lazy) — so bespoke budgets are free to check."""
    model, params = setup
    prompt = _prompts((9,))[0]
    # 5 usable pages of 4 = 20 tokens
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       page_size=PS, n_pages=6, prefill_chunk=4)
    assert eng.admissible(9, 11)            # 20 tokens = 5 pages
    assert not eng.admissible(9, 12)        # 21 tokens = 6 pages
    with pytest.raises(InvalidRequest, match=r"KV\s+pages") as ei:
        eng.submit(prompt, 12)
    # the message states the page math
    assert "ceil(21/4) = 6" in str(ei.value)
    assert "5 usable" in str(ei.value)
    # default pool (no n_pages): every slot can run to max_len
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       page_size=PS)
    assert eng.admissible(9, MAXLEN - 9)
    assert not eng.admissible(9, MAXLEN - 8)


def test_predictor_page_budget_falls_back_instead_of_400(setup, per_req):
    """Satellite 1: with paging, engine capacity is the page budget — a
    request it refuses but the per-request path can serve FALLS THROUGH
    (no wrong 400); a request neither path can serve honestly gets the
    page-math message; an eos-configured predictor never silently
    degrades into post-eos tokens."""
    model, params = setup
    prompt = _prompts((9,))[0]
    pred = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=2, kv_page_size=PS,
                             kv_n_pages=5, prefill_chunk=4)  # 16 tokens
    try:
        # 9 + 8 = 17 tokens > page budget, but per-request serves it
        req = {"tokens": prompt, "max_new_tokens": 8}
        before = _mx.snapshot()["counters"].get(
            "serving.engine.requests", 0)
        assert pred.predict(req) == per_req.predict(req)
        assert _mx.snapshot()["counters"].get(
            "serving.engine.requests", 0) == before  # engine untouched
        # neither path: per-request bucket also over max_len -> page math
        with pytest.raises(InvalidRequest, match="KV pages"):
            pred.predict({"tokens": prompt, "max_new_tokens": 24})
    finally:
        pred.stop()
    # eos-configured predictor: page-budget refusal must NOT degrade to
    # the (eos-less) per-request path — surfaced as the page-math 400
    eosp = GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                             decode_slots=2, kv_page_size=PS,
                             kv_n_pages=5, prefill_chunk=4, eos_id=1)
    try:
        with pytest.raises(InvalidRequest, match="KV pages"):
            eosp.predict({"tokens": prompt, "max_new_tokens": 8})
    finally:
        eosp.stop()


def test_paged_pool_reclaimed_after_retirement(eng_paged):
    """Every page is either free or resident in the prefix map once all
    requests retire — nothing leaks across the whole module's churn of
    admissions, retirements, prefix hits and shared pages. (One request
    runs first so the free-pages gauge publishes into THIS test's
    registry — the conftest swaps a fresh one per test.)"""
    eng_paged.submit(_prompts((7,), seed=41)[0], 3).result(timeout=120)
    assert len(eng_paged._free_pages) + len(eng_paged._prefix) == \
        eng_paged._usable
    assert _mx.snapshot()["gauges"]["serving.kv_pages_free"] == \
        len(eng_paged._free_pages)


# ------------------------------------------------------------- satellites
def test_paged_knob_gating(setup):
    model, params = setup
    with pytest.raises(ValueError, match="page_size must be >= 1"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN, page_size=0)
    with pytest.raises(ValueError, match="kv_n_pages must be >= 2"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                     page_size=PS, n_pages=1)
    with pytest.raises(ValueError, match="decode_slots"):
        GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                          kv_page_size=PS)


def test_serve_args_paged_config_validation():
    from fedml_tpu.config import Config

    cfg = Config.from_dict({"serve": {
        "decode_slots": 4, "kv_page_size": 16, "kv_n_pages": 65,
        "prefill_chunk": 32, "prefix_cache": True}})
    assert cfg.serve_args.extra["kv_page_size"] == 16
    # prefill_chunk: 0 is the documented whole-prompt-admission setting —
    # the validator must accept the value the README names
    Config.from_dict({"serve": {"decode_slots": 4, "kv_page_size": 16,
                                "prefill_chunk": 0}})
    for bad, msg in (
            ({"decode_slots": 2, "kv_page_size": 0}, "kv_page_size"),
            ({"kv_page_size": 8}, "requires decode_slots"),
            ({"kv_n_pages": 8}, "requires decode_slots"),
            ({"prefill_chunk": 8}, "requires decode_slots"),
            ({"prefix_cache": False}, "requires decode_slots"),
            ({"decode_slots": 2, "kv_page_size": 8, "prefix_cache": "y"},
             "boolean"),
            ({"decode_slots": 2, "kv_page_size": 8, "kv_n_pages": 1},
             ">= 2")):
        with pytest.raises(ValueError, match=msg):
            Config.from_dict({"serve": bad})


def test_lm_predictor_from_config_paged_knobs(setup):
    """The config bridge builds the engine's pool from YAML (structural —
    engine output identity is pinned above; predict here would only
    re-compile the same programs)."""
    from fedml_tpu.config import Config
    from fedml_tpu.serving import lm_predictor_from_config

    model, params = setup
    cfg = Config.from_dict({"serve": {
        "decode_slots": 2, "engine_max_len": MAXLEN, "kv_page_size": PS,
        "kv_n_pages": 20, "prefill_chunk": 4, "prefix_cache": False}})
    pred = lm_predictor_from_config(cfg, model, params)
    try:
        assert pred.engine is not None
        assert pred.engine.kv_page_size == PS
        assert pred.engine._n_pages == 20
        assert pred.engine._prefill_chunk == 4
        assert pred.engine._prefix_on is False
    finally:
        pred.stop()


def test_top_line_shows_page_occupancy_and_prefix_rate():
    from fedml_tpu.__main__ import _top_frame
    from fedml_tpu.utils.prometheus import (
        parse_prometheus, render_prometheus,
    )

    _mx.inc("serving.tokens_total", 42)
    _mx.set_gauge("serving.kv_pages_budget", 20)
    _mx.set_gauge("serving.kv_pages_free", 15)
    _mx.inc("serving.prefix_hits", 3)
    _mx.inc("serving.prefix_misses", 1)
    snap = parse_prometheus(render_prometheus(_mx.snapshot()))
    frame = _top_frame(snap, "test")
    assert "pages 5/20 (25%)" in frame
    assert "prefix 75%" in frame
    assert "walk" not in frame          # no step frame drained yet
    _mx.inc("serving.engine.steps", 10)
    _mx.inc("serving.engine.page_steps", 41)
    _mx.set_gauge("serving.engine.table_pages", 16 * 128)
    snap = parse_prometheus(render_prometheus(_mx.snapshot()))
    assert "walk 0.2%" in _top_frame(snap, "test")      # 41 / (10 x 2,048)
