"""Pallas paged-attention decode kernel + greedy-exact speculative
decoding (ISSUE 11).

The contracts the decode-speed legs live by:
- KERNEL TOKEN IDENTITY: the fused Pallas step (pages read in place via
  the page table, online softmax — ops/paged_attention.py, exercised
  for real on CPU through interpret mode) emits exactly the gather
  path's tokens — greedy and seeded sampling, mid-flight admission/
  retirement over shared prefix pages, and on an mp=2 mesh where the
  kernel shard_maps over the pool's heads axis;
- SPECULATION TOKEN IDENTITY: n-gram self-drafted speculation emits
  exactly the speculation-off stream (greedy-exact acceptance stated as
  an algorithm), including rejection-heavy traffic where every window
  rolls the cache write position back across page boundaries, eos
  retirement, and seeded sampling (the per-position rng schedule is the
  plain step's);
- bounded programs: the kernel is still ONE step program; speculation is
  ONE verify program and ZERO plain-step programs;
- knobs are refused wherever they would be silently ignored.

Jitted programs dominate wall clock, so engines and the per-request
reference are MODULE-scoped and shared (the PR 6-8 budget pattern);
tests needing bespoke engines (mp=2, eos) build the smallest thing that
proves the point.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.utils import metrics as _mx

V, D, L, H, FF = 96, 64, 2, 4, 128
MAXLEN = 32
PS = 4


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
def eng_gather(setup):
    """The gather-path paged engine: THE oracle both legs are pinned
    against (itself pinned equal to the per-request path in
    test_paged_engine.py)."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4).start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def eng_kernel(setup):
    """Same engine, fused Pallas step."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4,
                       paged_kernel=True).start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def eng_spec(setup):
    """Same engine, n-gram speculation: spec_k=3 windows over 4-token
    pages, so every verify window straddles a page boundary and every
    rejection rolls the write position back across one."""
    model, params = setup
    eng = DecodeEngine(model, params, n_slots=3, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4,
                       spec_decode="ngram", spec_k=3).start()
    yield eng
    eng.stop()


def _prompts(ns, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, V, n).tolist() for n in ns]


def _want(per_req, prompts, budgets):
    return [per_req.predict({"tokens": p, "max_new_tokens": b})
            ["generated_tokens"] for p, b in zip(prompts, budgets)]


def _wave(eng, prompts, budgets, **kw):
    tickets = [eng.submit(p, b, **kw) for p, b in zip(prompts, budgets)]
    return [t.result(timeout=120) for t in tickets]


# -------------------------------------------------------------- kernel leg
def test_kernel_greedy_identical_mid_flight_shared_pages(
        setup, per_req, eng_gather, eng_kernel):
    """PINNED: 6 prompts — two sharing an 8-token prefix (shared pages +
    a prefix hit mid-run) — through 3 kernel-stepped slots with chunked
    prefill, admissions and retirements interleaving mid-flight, vs the
    per-request path AND the gather-path paged engine. Token for
    token."""
    shared = _prompts((8,), seed=9)[0]
    prompts = _prompts((6, 10, 8, 5)) + [shared + p
                                         for p in _prompts((3, 5), seed=2)]
    budgets = [4, 7, 5, 6, 4, 5]
    want = _want(per_req, prompts, budgets)
    assert _wave(eng_gather, prompts, budgets) == want
    assert _wave(eng_kernel, prompts, budgets) == want


def test_kernel_seeded_sampling_identical(eng_gather, eng_kernel):
    """The kernel changes the attention *schedule*, not the rng one:
    same (seed, temperature) draws the same tokens as the gather path,
    and the same-seed/diff-seed contract holds within the kernel
    engine."""
    prompt = _prompts((8,), seed=11)[0]
    w7, w8 = _wave(eng_gather, [prompt] * 2, [8] * 2,
                   temperature=2.0, seed=7), None
    w8 = _wave(eng_gather, [prompt], [8], temperature=2.0, seed=8)[0]
    a = eng_kernel.submit(prompt, 8, temperature=2.0, seed=7)
    c = eng_kernel.submit(prompt, 8, temperature=2.0, seed=8)
    a, c = a.result(timeout=120), c.result(timeout=120)
    assert a == w7[0] == w7[1]
    assert c == w8
    assert a != c


def test_kernel_mp2_token_identical(setup, eng_gather):
    """Kernel engine on an {"mp": 2} mesh (conftest forces 8 virtual CPU
    devices): weights Megatron-split, the page POOL sharded on its heads
    axis (partition.paged_kv_cache_spec), and the Pallas kernel runs
    INSIDE a shard_map over that same axis — each device attends its own
    heads, page table replicated. Greedy output token-identical to the
    unmeshed gather path."""
    from fedml_tpu.parallel.mesh import make_mesh

    model, params = setup
    prompts = _prompts((6, 10, 8))
    want = _wave(eng_gather, prompts, [5] * 3)
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4, paged_kernel=True,
                       mesh=make_mesh({"mp": 2})).start()
    try:
        assert _wave(eng, prompts, [5] * 3) == want
    finally:
        eng.stop()


def test_kernel_retrace_guard(eng_kernel):
    """The fused step is still ONE program; a fresh wave (sampling on,
    new seeds/temps, prefix hits and misses) must not add a compile."""
    counts = eng_kernel.program_counts()
    assert counts["step"] == 1, counts
    assert counts["admit"] is None or counts["admit"] <= 3, counts
    for t in [eng_kernel.submit(p, 4, temperature=1.3, seed=i)
              for i, p in enumerate(_prompts((6, 10, 3, 12), seed=4))]:
        t.result(timeout=120)
    assert eng_kernel.program_counts() == counts, "retrace"


# --------------------------------------------------------- speculation leg
def test_spec_greedy_identical_and_rollback_across_pages(
        eng_gather, eng_spec):
    """PINNED: speculation-on greedy == speculation-off on BOTH traffic
    shapes — acceptance-friendly (constant-token prompts whose greedy
    continuations loop; drafts must actually be accepted) and
    rejection-heavy (random prompts; most windows reject, so the write
    position rolls back across page boundaries every iteration —
    spec_k=3 windows over 4-token pages straddle one by construction).
    Mid-flight churn: all 6 requests share 3 slots."""
    friendly = [[t] * 8 for t in (5, 40, 77)]
    hostile = _prompts((6, 10, 7), seed=13)
    prompts = friendly + hostile
    budgets = [7, 6, 8, 6, 7, 5]
    want = _wave(eng_gather, prompts, budgets)
    c0 = _mx.snapshot()["counters"]
    got = _wave(eng_spec, prompts, budgets)
    c1 = _mx.snapshot()["counters"]
    assert got == want
    accepted = c1.get("serving.spec.accepted", 0) - c0.get(
        "serving.spec.accepted", 0)
    proposed = c1.get("serving.spec.proposed", 0) - c0.get(
        "serving.spec.proposed", 0)
    # drafts were really accepted (the friendly lane) AND really
    # rejected (the hostile lane exercised rollback)
    assert accepted >= 1, (accepted, proposed)
    assert proposed > accepted, (accepted, proposed)


def test_spec_seeded_sampling_identical(eng_gather, eng_spec):
    """Greedy-exact generalizes to any deterministic pick schedule: the
    verify window folds the SAME per-position keys the plain step does,
    so seeded sampling is pinned across spec on/off too."""
    prompt = _prompts((8,), seed=21)[0]
    want = eng_gather.submit(prompt, 8, temperature=1.7,
                             seed=5).result(timeout=120)
    got = eng_spec.submit(prompt, 8, temperature=1.7,
                          seed=5).result(timeout=120)
    other = eng_spec.submit(prompt, 8, temperature=1.7,
                            seed=6).result(timeout=120)
    assert got == want
    assert got != other


def test_spec_eos_retirement_identical(setup, eng_gather):
    """A window that produces eos mid-acceptance must stop emitting AT
    the eos token exactly as plain decode does (the in-window budget/eos
    clamps). eos chosen from an observed output so it actually fires
    (the warm module engine supplies the observation)."""
    model, params = setup
    prompt = [5] * 8
    full = eng_gather.submit(prompt, 8).result(timeout=120)
    eos = full[2]          # retires mid-request
    outs = []
    for spec in ("off", "ngram"):
        eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                           page_size=PS, prefill_chunk=4,
                           spec_decode=spec, spec_k=3, eos_id=eos).start()
        try:
            outs.append(eng.submit(prompt, 8).result(timeout=120))
        finally:
            eng.stop()
    assert outs[0] == outs[1]
    assert outs[0][-1] == eos and len(outs[0]) < 8


def test_kernel_spec_composed_identical(setup, eng_gather):
    """The two legs COMPOSE: speculation's verify windows run through
    the multi-query (C = spec_k+1) Pallas kernel — the one configuration
    that exercises the kernel's C > 1 masking (query i at pos+i against
    the window's own writes). Output still token-identical to the plain
    gather engine, with drafts genuinely accepted and rejected."""
    model, params = setup
    prompts = [[5] * 8] + _prompts((6, 9), seed=17)
    budgets = [7, 5, 6]
    want = _wave(eng_gather, prompts, budgets)
    eng = DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                       page_size=PS, prefill_chunk=4, paged_kernel=True,
                       spec_decode="ngram", spec_k=3).start()
    c0 = _mx.snapshot()["counters"]
    try:
        assert _wave(eng, prompts, budgets) == want
        counts = eng.program_counts()
    finally:
        eng.stop()
    c1 = _mx.snapshot()["counters"]
    assert counts["verify"] == 1 and counts["step"] == 0, counts
    prop = c1.get("serving.spec.proposed", 0) - c0.get(
        "serving.spec.proposed", 0)
    acc = c1.get("serving.spec.accepted", 0) - c0.get(
        "serving.spec.accepted", 0)
    assert 0 < acc < prop, (acc, prop)


def test_spec_retrace_guard(eng_spec):
    """Speculation is ONE verify-window program and ZERO plain-step
    programs, stable across a fresh wave."""
    counts = eng_spec.program_counts()
    assert counts["verify"] == 1, counts
    assert counts["step"] == 0, counts
    # chunk remainders bucket to pow2s the module's waves already
    # compiled — a fresh wave (sampling on, new seeds) adds nothing
    for t in [eng_spec.submit(p, 4, temperature=0.9, seed=i)
              for i, p in enumerate(_prompts((6, 10, 3), seed=8))]:
        t.result(timeout=120)
    assert eng_spec.program_counts() == counts, "retrace"


# ------------------------------------------------------------- satellites
def test_knob_gating(setup):
    """Both legs live in the engine — asking for either where it would
    be silently ignored is refused (predictor without slots), and a bad
    value is refused wherever it is given."""
    model, params = setup
    with pytest.raises(ValueError, match="'off' or 'ngram'"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                     page_size=PS, spec_decode="draft")
    with pytest.raises(ValueError, match="spec_k"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                     page_size=PS, spec_decode="ngram", spec_k=0)
    with pytest.raises(ValueError, match="paged_kernel.*decode_slots"):
        GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                          paged_kernel=True)
    with pytest.raises(ValueError, match="spec_decode.*decode_slots"):
        GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                          spec_decode="ngram")


def test_serve_args_decode_speed_validation():
    from fedml_tpu.config import Config

    import yaml

    cfg = Config.from_dict({"serve": {
        "decode_slots": 2, "kv_page_size": PS, "paged_kernel": True,
        "spec_decode": "ngram", "spec_k": 4}})
    assert cfg.serve_args.extra["paged_kernel"] is True
    assert cfg.serve_args.extra["spec_k"] == 4
    # YAML 1.1 reads unquoted `off` as False — the documented disable
    # spelling must still load (normalized), and `true` must name the
    # quoting problem instead of accepting a non-mode
    y = yaml.safe_load("serve:\n  decode_slots: 2\n  kv_page_size: 4\n"
                       "  spec_decode: off\n")
    assert y["serve"]["spec_decode"] is False      # the YAML-1.1 trap
    assert Config.from_dict(y).serve_args.extra["spec_decode"] == "off"
    with pytest.raises(ValueError, match="quote"):
        Config.from_dict({"serve": {"decode_slots": 2, "kv_page_size": PS,
                                    "spec_decode": True}})
    for bad, msg in (
            ({"paged_kernel": True}, "requires decode_slots"),
            ({"decode_slots": 2, "kv_page_size": PS,
              "paged_kernel": "y"}, "boolean"),
            ({"spec_decode": "ngram"}, "requires decode_slots"),
            ({"decode_slots": 2, "kv_page_size": PS,
              "spec_decode": "draft"}, "'off' or 'ngram'"),
            ({"decode_slots": 2, "kv_page_size": PS, "spec_k": 4},
             "requires spec_decode"),
            ({"decode_slots": 2, "kv_page_size": PS,
              "spec_decode": "ngram", "spec_k": 0}, ">= 1")):
        with pytest.raises(ValueError, match=msg):
            Config.from_dict({"serve": bad})


def test_lm_predictor_from_config_decode_speed_knobs(setup):
    """The one shared knob mapping carries both legs (config and deploy
    surfaces cannot drift) — structural; identity is pinned above."""
    from fedml_tpu.config import Config
    from fedml_tpu.serving import lm_predictor_from_config

    model, params = setup
    cfg = Config.from_dict({"serve": {
        "decode_slots": 2, "engine_max_len": MAXLEN, "kv_page_size": PS,
        "prefill_chunk": 4, "paged_kernel": True,
        "spec_decode": "ngram", "spec_k": 2}})
    pred = lm_predictor_from_config(cfg, model, params)
    try:
        assert pred.engine is not None
        assert pred.engine.kv_page_size == PS
        assert pred.engine._kernel_on is True
        assert pred.engine._spec_on is True
        assert pred.engine._spec_k == 2
    finally:
        pred.stop()


def test_top_line_shows_accept_rate():
    from fedml_tpu.__main__ import _top_frame
    from fedml_tpu.utils.prometheus import (
        parse_prometheus, render_prometheus,
    )

    _mx.inc("serving.tokens_total", 42)
    _mx.inc("serving.spec.proposed", 40)
    _mx.inc("serving.spec.accepted", 13)
    snap = parse_prometheus(render_prometheus(_mx.snapshot()))
    frame = _top_frame(snap, "test")
    assert "spec 32%" in frame


def test_diagnosis_spec_smoke(capsys):
    """The required probe is --only-compatible and green: repetitive
    traffic through a spec engine — accepted > 0, tokens identical to
    spec-off, bounded programs."""
    import json

    from fedml_tpu.__main__ import main

    rc = main(["diagnosis", "--only", "serving_spec_smoke"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    chk = out["checks"]["serving_spec_smoke"]
    assert chk["ok"] and chk["accepted"] >= 1
    assert chk["programs"]["verify"] in (None, 1)
