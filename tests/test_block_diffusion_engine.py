"""Generation by diffusion over blocks through the decode engine
(serving/engine.py `_block_all`, llm/decode.py's window program) against the
plain reference's cache-less loop (chipbench/reference/sdar_30b_a3b_pp8.py
`generate`: a full forward every denoising forward) on seeded random weights
at tiny sizes: 2 layers, width 64, 8 heads over 2 KV heads, 8 experts top 2
under a softmax router, vocabulary 128, block 4. Tokens and the forward that
unmasked each are identical, confidences agree to float32 round-off. One
engine (and its compiled programs) serves every case of the file; a case that
needs other weights swaps their VALUES in while the engine is idle."""
import jax
import numpy as np
import pytest

from chipbench import inputs, manifest
from chipbench.reference import sdar_30b_a3b_pp8 as ref
from fedml_tpu.llm.decode import stack_blocks
from fedml_tpu.serving.engine import DecodeEngine
from fedml_tpu.serving.predictor import InvalidRequest
from fedml_tpu.utils import metrics as mx

CONF_TOL = 2e-5         # float32 programs of different shape, one softmax
CFG = manifest.load_json(manifest.HERE / "configs" / "sdar_30b_a3b_pp8.json")
MODEL = {**CFG["model"], **CFG["rehearse"]["model"]}
MASK = MODEL["mask_token_id"]


def prompt(n: int, seed: int = 0) -> list:
    return [int(v) for v in np.random.RandomState(seed).randint(1, MASK, n)]


@pytest.fixture(scope="module")
def served():
    lm, _spec = manifest.find("models", "sdar_moe")(MODEL)
    params = inputs.init_tree(inputs.param_shapes(lm), 3, 1.0, "float32")
    eng = DecodeEngine(lm, params, n_slots=3, max_len=64, page_size=4,
                       prefill_chunk=8).start()
    yield lm, params, eng
    eng.stop()


def both(eng, params, toks, new, steps=None, threshold=None, eos=None):
    """(engine tokens, their notes, reference tokens, their notes)."""
    t = eng.submit(toks, new, denoising_steps=steps,
                   confidence_threshold=threshold)
    got = t.result(timeout=300)
    notes = [t.note(i) for i in range(len(got))]
    want, wnotes = ref.generate(params, toks, new, MODEL, steps, threshold,
                                eos)
    return got, notes, want, wnotes


def assert_same(got, notes, want, wnotes):
    assert got == want
    assert [f for f, _ in notes] == [f for f, _ in wnotes]
    assert max(abs(a - b) for (_, a), (_, b) in zip(notes, wnotes)) < CONF_TOL


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_static_rule_generates_the_references_tokens(served, steps):
    _lm, params, eng = served
    got, notes, want, wnotes = both(eng, params, prompt(9, steps), 14, steps)
    assert_same(got, notes, want, wnotes)
    assert len(got) == 14
    # at least ceil(4 / steps) tokens a forward: a block takes `steps` forwards
    assert max(f for f, _ in notes) == steps - 1


@pytest.mark.parametrize("plen", [8, 5, 6, 7])
def test_a_prompt_may_end_anywhere_in_a_block(served, plen):
    """Prompt lengths 0, 1, 2 and 3 mod 4: the prompt's tail opens the first
    block and is not streamed back."""
    _lm, params, eng = served
    got, notes, want, wnotes = both(eng, params, prompt(plen, plen), 7, 2)
    assert_same(got, notes, want, wnotes)
    assert len(got) == 7


def test_a_prompt_shorter_than_a_block_is_admitted_through_an_empty_chunk(
        served):
    _lm, params, eng = served
    assert_same(*both(eng, params, prompt(3, 33), 6, 4))


def test_the_engines_default_is_a_token_a_forward_at_least(served):
    _lm, params, eng = served
    got, notes, want, wnotes = both(eng, params, prompt(8, 44), 8)
    assert_same(got, notes, want, wnotes)
    assert sorted({f for f, _ in notes}) == [0, 1, 2, 3]


@pytest.fixture()
def peaked(served):
    """The same engine with the head's kernel scaled up, so that some
    confidences stand over a threshold and some under it (crafted logits);
    the engine's own weights come back afterwards."""
    _lm, params, eng = served
    crafted = {**params, "lm_head": {
        "kernel": params["lm_head"]["kernel"] * 12.0}}
    sound = eng.params
    eng.params = stack_blocks(crafted, MODEL["num_hidden_layers"])
    yield crafted, eng
    eng.params = sound


def test_the_dynamic_rule_unmasks_what_is_confident_and_at_least_its_share(
        peaked):
    crafted, eng = peaked
    before = mx.snapshot()["counters"]
    got, notes, want, wnotes = both(eng, crafted, prompt(8, 5), 24, 4, 0.9)
    assert_same(got, notes, want, wnotes)
    conf = np.array([c for _, c in notes])
    assert (conf > 0.9).any() and (conf < 0.9).any()
    # some forward unmasked more than its one token, some block took all four
    firsts = sum(f == 0 for f, _ in notes)
    assert 6 < firsts < 24
    after = mx.snapshot()["counters"]
    d = lambda k: after.get(k, 0) - before.get(k, 0)
    # every generated position was unmasked exactly once
    assert d("serving.engine.unmasked_tokens") == 24
    assert d("serving.engine.block_positions") == 4 * d(
        "serving.engine.block_forwards")
    # every block but the last is committed, by a forward of its own
    assert d("serving.engine.commit_forwards") == 5


def test_an_eos_inside_a_block_ends_the_answer_there(served):
    lm, params, _eng = served
    toks = prompt(6, 9)
    free, _ = ref.generate(params, toks, 12, MODEL, 2)
    eos = free[5]                   # inside the second block
    cut = free.index(eos) + 1
    eng = DecodeEngine(lm, params, n_slots=2, max_len=64, page_size=4,
                       prefill_chunk=8, eos_id=eos).start()
    try:
        t = eng.submit(toks, 12, denoising_steps=2)
        got = t.result(timeout=300)
        assert got == free[:cut] and got[-1] == eos
        want, _ = ref.generate(params, toks, 12, MODEL, 2, eos=eos)
        assert got == want
        # its pages came back with it
        assert len(eng._free_pages) + len(eng._prefix) == eng._usable
    finally:
        eng.stop()


def test_three_slots_batched_generate_what_each_does_alone(served):
    _lm, params, eng = served
    asks = [(prompt(10, 1), 10, 2), (prompt(7, 2), 13, 4),
            (prompt(13, 3), 6, 1)]
    alone = [eng.submit(p, n, denoising_steps=s).result(timeout=300)
             for p, n, s in asks]
    tickets = [eng.submit(p, n, denoising_steps=s) for p, n, s in asks]
    assert [t.result(timeout=300) for t in tickets] == alone
    for (p, n, s), got in zip(asks, alone):
        assert got == ref.generate(params, p, n, MODEL, s)[0]


def test_a_prefix_hit_generates_what_the_cold_run_did(served):
    _lm, params, eng = served
    doc = prompt(16, 77)
    before = mx.snapshot()["counters"]
    cold = eng.submit(doc + prompt(3, 78), 8, denoising_steps=2)
    cold_toks = cold.result(timeout=300)
    warm = eng.submit(doc + prompt(3, 78), 8, denoising_steps=2)
    assert warm.result(timeout=300) == cold_toks
    other = eng.submit(doc + prompt(6, 79), 8, denoising_steps=2)
    assert other.result(timeout=300) == ref.generate(
        params, doc + prompt(6, 79), 8, MODEL, 2)[0]
    after = mx.snapshot()["counters"]
    d = lambda k: after.get(k, 0) - before.get(k, 0)
    # whole pages only, and a page is a whole number of blocks: 16 tokens
    assert warm.prefill["hit_pages"] == 4 and other.prefill["hit_pages"] == 4
    assert d("serving.prefix_hit_tokens") == 32


def test_the_counters_count_block_forwards_as_they_count_steps(served):
    _lm, params, eng = served
    before = mx.snapshot()["counters"]
    eng.submit(prompt(8, 66), 8, denoising_steps=2).result(timeout=300)
    after = mx.snapshot()["counters"]
    d = lambda k: after.get(k, 0) - before.get(k, 0)
    # two blocks: 2 denoising forwards + a commit, then 2 (the last block
    # is not committed: nothing reads it)
    assert d("serving.engine.block_forwards") == 5
    assert d("serving.engine.slot_steps") == 5
    assert d("serving.engine.commit_forwards") == 1
    assert d("serving.engine.unmasked_tokens") == 8
    assert d("serving.engine.block_positions") == 20
    # the windows attend 12, 12, 12, 16, 16 positions
    assert d("serving.engine.block_context") == 68
    # 8 prefilled queries see their blocks to the end, then 4 queries a
    # forward see their window's context
    assert d("serving.engine.context_keys") == (4 * 4 + 4 * 8) + 4 * 68
    # 4 live rows x top 2 x 2 layers a forward; at most 8 experts a layer
    assert d("serving.engine.moe_pairs") == 5 * 16
    assert 5 * 2 <= d("serving.engine.moe_experts_live") <= 5 * 2 * 8
    assert d("serving.engine.completions") == 1
    assert eng.program_counts()["step"] == 0
    assert eng.program_counts()["block"] == 1


def test_a_request_names_its_denoising_or_is_refused_with_a_sentence(served):
    _lm, _params, eng = served
    with pytest.raises(InvalidRequest, match="denoising_steps must be 1 .. 4"):
        eng.submit(prompt(4), 4, denoising_steps=5)
    with pytest.raises(InvalidRequest, match=r"lie in \(0, 1\] or be null"):
        eng.submit(prompt(4), 4, confidence_threshold=1.5)
    with pytest.raises(InvalidRequest, match="must be an integer"):
        eng.submit(prompt(4), 4, denoising_steps="two")


def test_the_paged_kernel_serves_the_same_tokens(served):
    lm, params, _eng = served
    eng = DecodeEngine(lm, params, n_slots=2, max_len=64, page_size=4,
                       prefill_chunk=8, paged_kernel=True).start()
    try:
        for toks, new, steps in ((prompt(9, 11), 11, 2),
                                 (prompt(22, 12), 9, 4)):
            t = eng.submit(toks, new, denoising_steps=steps)
            got = t.result(timeout=300)
            want, wnotes = ref.generate(params, toks, new, MODEL, steps)
            assert_same(got, [t.note(i) for i in range(len(got))], want,
                        wnotes)
    finally:
        eng.stop()


def test_temperature_sampling_is_seeded(served):
    _lm, _params, eng = served
    toks = prompt(8, 21)
    a = eng.submit(toks, 8, temperature=1.0, seed=5,
                   denoising_steps=2).result(timeout=300)
    b = eng.submit(toks, 8, temperature=1.0, seed=5,
                   denoising_steps=2).result(timeout=300)
    c = eng.submit(toks, 8, temperature=1.0, seed=6,
                   denoising_steps=2).result(timeout=300)
    assert a == b and a != c and len(a) == 8
    assert jax.tree.leaves(eng._carry["blk"])[0].shape == (3, 4)
