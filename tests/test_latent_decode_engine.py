"""The latent page pool through the ENGINE: a prefix hit against a miss, and
the engine's counters. tests/test_latent_decode.py's longest case, moved here
unchanged (PR 36) so that `--dist loadfile` spreads the two over workers; the
model and its seeded weights are that file's."""
import jax.numpy as jnp
import numpy as np
from test_latent_decode import LAT, PAGE, seeded  # noqa: F401 (a fixture)

from fedml_tpu.utils import metrics as mx


def test_the_engine_serves_it_and_a_prefix_hit_reads_what_a_miss_wrote(
        seeded):
    """Three asks of one document: the first prefills it (a miss), the next
    two find its pages (latents AND indexer keys) and prefill their
    questions alone; each is greedy-decoded as the whole-sequence forward
    would, and the counters say what was attended of what was live."""
    from fedml_tpu.serving.engine import DecodeEngine

    m, params, _ = seeded
    rs = np.random.RandomState(0)
    doc = [int(v) for v in rs.randint(1, 50, 24)]

    def greedy(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            seq.append(int(jnp.argmax(m.apply(
                {"params": params}, jnp.asarray(seq)[None])[0, -1])))
        return seq[len(prompt):]

    before = dict(mx.snapshot()["counters"])
    eng = DecodeEngine(m, params, n_slots=3, max_len=64, page_size=PAGE,
                       prefill_chunk=8, paged_kernel=True).start()
    try:
        assert set(eng._carry["cache"]) == {"kv", "ik"}
        assert eng._carry["cache"]["kv"].shape[2:] == (PAGE, LAT.width)
        for _ in range(3):
            prompt = doc + [int(v) for v in rs.randint(1, 50, 5)]
            assert eng.submit(prompt, 6).result(timeout=300) == greedy(
                prompt, 6)
    finally:
        eng.stop()
    after = mx.snapshot()["counters"]
    d = lambda k: after.get(k, 0) - before.get(k, 0)
    assert d("serving.engine.completions") == 3
    assert d("serving.prompt_tokens") == 3 * 29
    assert d("serving.prefix_hit_tokens") == 2 * 24
    assert 0 < d("serving.engine.selected_keys") < d(
        "serving.engine.context_keys")
