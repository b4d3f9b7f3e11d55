"""Serving density (ISSUE 16): int8 KV pages, batched admission, and
their compositions.

The contracts under test:
- kv_quant="off" (the default) IS the pre-knob engine: the pool stays in
  the compute dtype and no scale planes ride the carry — density is
  opt-in, never a silent quality tax;
- int8 pages keep greedy tokens: match bar 0.99 against the baseline on
  this workload (empirically identical at these dims), with
  per-(page, head) scales that RESET when a page is freshly claimed
  (offset-0 write) — decoded tokens cannot depend on page-allocation
  history and quantization cannot degrade over an engine's lifetime;
- density is measurable, not asserted: the serving.kv_bytes_per_slot
  gauge for the int8 pool (f32 scales included — they are the layout's
  real overhead) is >= 2x smaller than the baseline's at equal geometry;
- admit_batch groups same-bucket admissions into ONE batched chunk
  program, token-identical to serial admission, visible in
  program_counts() and the serving.engine.admit_batch histogram;
- spec-decode composes with int8 pages token-identically (the
  verify-and-rollback rewrite requantizes through the same scale path);
- knob gating: kv_quant / admit_batch / affinity_routing hard-fail when
  their substrate knob is missing — at the serve_args layer AND the
  engine/predictor ctors — instead of being silently ignored.

Engines are MODULE-scoped and shared (tier-1 budget discipline — see
test_paged_engine.py); structural and density checks use UNSTARTED
engines (the carry and the kv_bytes_per_slot gauge are built in
__init__, and construction never compiles).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.transformer import TransformerLM
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.knobs import validate_serve_args
from fedml_tpu.serving.predictor import GreedyLMPredictor
from fedml_tpu.utils import metrics as _mx

V, D, L, H, FF = 96, 64, 2, 4, 128
MAXLEN = 32
PS = 4
NEW = 12

_rs = np.random.RandomState(7)
PROMPTS = [_rs.randint(1, V, 8).tolist() for _ in range(4)]
# repetitive prompts so ngram speculation actually drafts
SPEC_PROMPTS = [(p[:4] * 3)[:10] for p in PROMPTS]

KW = dict(n_slots=4, max_len=MAXLEN, page_size=PS, prefill_chunk=4,
          fetch_chunk=1, prefix_cache=False)


@pytest.fixture(scope="module")
def setup():
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          d_ff=FF, scan_layers=True)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 10), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def base_outs(setup):
    """Baseline (unquantized) greedy outputs — the engine lives only long
    enough to produce them; every comparison below is against these."""
    model, params = setup
    eng = DecodeEngine(model, params, **KW).start()
    try:
        return [eng.submit(p, NEW).result(timeout=300) for p in PROMPTS]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def eng_int8(setup):
    """THE shared int8 engine: identity, spec-composition, and batched-
    admission tests all compare against its outputs."""
    model, params = setup
    eng = DecodeEngine(model, params, kv_quant="int8", **KW).start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def int8_outs(eng_int8):
    return [eng_int8.submit(p, NEW).result(timeout=300) for p in PROMPTS]


# ------------------------------------------------------------ quant off
def test_kv_quant_off_is_the_pre_knob_engine(setup):
    """`off` must mean STRUCTURALLY off: same pool dtype as compute, no
    scale planes in the carry — not int8 with a 1.0 scale. (Token
    identity of the off engine rides test_paged_engine's baseline-vs-
    per-request pins; this pins that the knob default changes nothing.)"""
    model, params = setup
    eng = DecodeEngine(model, params, kv_quant="off", **KW)  # unstarted
    cache = eng._carry["cache"]
    assert cache["k"].dtype != jnp.int8
    assert "ks" not in cache and "vs" not in cache


def test_int8_carry_layout(setup, eng_int8):
    """int8 pool + f32 per-(page, head) scales riding the carry."""
    cache = eng_int8._carry["cache"]
    assert cache["k"].dtype == jnp.int8 and cache["v"].dtype == jnp.int8
    assert cache["ks"].dtype == jnp.float32
    assert cache["ks"].shape == (L, eng_int8._n_pages, H)


# ------------------------------------------------------- token identity
def test_int8_greedy_match_rate(base_outs, int8_outs):
    """The headline quality bar: >= 0.99 greedy agreement with the
    unquantized engine (identical at these dims; the bench measures the
    teacher-forced rate at larger dims)."""
    total = sum(len(o) for o in base_outs)
    matched = sum(a == b for ob, oq in zip(base_outs, int8_outs)
                  for a, b in zip(ob, oq))
    assert matched / total >= 0.99, (matched, total)


# -------------------------------------------------------------- density
def test_kv_bytes_per_slot_gauge_halves(setup):
    """>= 2x decode slots at fixed KV HBM: bytes/slot off the gauge, int8
    (scales included) vs baseline, same geometry. Unstarted engines —
    the gauge is set in __init__."""
    model, params = setup
    DecodeEngine(model, params, **KW)
    base = _mx.snapshot()["gauges"]["serving.kv_bytes_per_slot"]
    DecodeEngine(model, params, kv_quant="int8", **KW)
    quant = _mx.snapshot()["gauges"]["serving.kv_bytes_per_slot"]
    assert quant * 2 <= base, (quant, base)


# ----------------------------------------------------- batched admission
def test_admit_batch_token_identical_and_counted(setup, int8_outs):
    """A same-bucket burst admits through ONE batched chunk program,
    token-identical to serial admission; the program registers in
    program_counts() and the group size lands in the
    serving.engine.admit_batch histogram."""
    model, params = setup
    eng = DecodeEngine(model, params, kv_quant="int8", admit_batch=4,
                       **KW).start()
    try:
        tickets = [eng.submit(p, NEW) for p in PROMPTS]
        outs = [t.result(timeout=300) for t in tickets]
        counts = eng.program_counts()
    finally:
        eng.stop()
    assert outs == int8_outs
    assert counts.get("admit_batch", 0) >= 1, counts
    hist = _mx.snapshot()["histograms"]["serving.engine.admit_batch"]
    assert hist["count"] >= 1, hist


# ----------------------------------------------------- spec composition
def test_spec_decode_composes_with_int8(setup, eng_int8):
    """ngram speculation over int8 pages: verify-and-rollback rewrites
    requantize through the same scale path, so output stays token-
    identical to the non-speculative int8 engine."""
    model, params = setup
    want = [eng_int8.submit(p, NEW).result(timeout=300)
            for p in SPEC_PROMPTS]
    eng = DecodeEngine(model, params, kv_quant="int8",
                       spec_decode="ngram", spec_k=2, **KW).start()
    try:
        got = [eng.submit(p, NEW).result(timeout=300)
               for p in SPEC_PROMPTS]
        counts = eng.program_counts()
    finally:
        eng.stop()
    assert got == want
    assert counts.get("verify", 0) >= 1, counts  # speculation really ran


# ---------------------------------------------------------- knob gating
def test_serve_args_gating():
    """serve_args-layer refusal: each density knob without its substrate
    is a hard error naming the missing knob, never a silent no-op."""
    with pytest.raises(ValueError, match="kv_quant requires decode_slots"):
        validate_serve_args({"kv_quant": "int8"})
    with pytest.raises(ValueError, match="not a mode"):
        validate_serve_args({"kv_quant": True, "decode_slots": 2,
                             "kv_page_size": 4})
    with pytest.raises(ValueError, match="decode_slots"):
        validate_serve_args({"admit_batch": 4})
    with pytest.raises(ValueError, match="prefix"):
        validate_serve_args({"affinity_routing": True})
    with pytest.raises(ValueError, match="prefix"):
        validate_serve_args({"affinity_routing": True, "decode_slots": 2,
                             "kv_page_size": 4, "prefix_cache": False})
    # and the composed happy path is clean
    validate_serve_args({"decode_slots": 2, "kv_page_size": 4,
                         "kv_quant": "int8", "admit_batch": 4,
                         "affinity_routing": True})


def test_ctor_gating(setup):
    """The engine and predictor enforce the same substrate requirements
    for callers that bypass serve_args."""
    model, params = setup
    with pytest.raises(ValueError, match="admit_batch"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                     page_size=PS, admit_batch=0)
    with pytest.raises(ValueError, match="kv_quant"):
        DecodeEngine(model, params, n_slots=2, max_len=MAXLEN,
                     page_size=PS, kv_quant="int4")
    with pytest.raises(ValueError, match="kv_quant.*decode_slots"):
        GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                          kv_quant="int8")
    with pytest.raises(ValueError, match="decode_slots"):
        GreedyLMPredictor(model, params, max_len=MAXLEN, kv_cache=True,
                          admit_batch=2)
