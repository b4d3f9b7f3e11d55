"""The traffic schedule is a pure function of the mix and the seed: the mix
fixes sizes, arrival gaps and their order, the seed draws the token ids."""
import numpy as np
import pytest

from chipbench import loadgen, manifest

MIX = manifest.load_json(manifest.HERE / "traffic" / "chat.json")


def sched(seed, seconds=30.0):
    return loadgen.build_schedule(MIX, seconds, seed, vocab=50304)


def test_same_seed_same_schedule():
    assert sched(7) == sched(7)


def test_a_large_seed_is_taken():
    assert sched(2 ** 31 + 12345)


def test_another_seed_is_the_same_schedule_with_other_tokens():
    a, b = sched(1), sched(2)
    assert [(p.due, len(p.tokens), p.max_new) for p in a] == \
        [(p.due, len(p.tokens), p.max_new) for p in b]
    assert [p.tokens for p in a[:5]] != [p.tokens for p in b[:5]]


def test_another_schedule_seed_is_another_order_of_the_same_work():
    other = loadgen.build_schedule(
        {**MIX, "schedule_seed": MIX["schedule_seed"] + 1}, 30.0, 1, 50304)
    a = sched(1)
    assert [p.due for p in a] != [p.due for p in other]
    assert abs(len(a) - len(other)) <= 3
    n = min(len(a), len(other)) - 3
    la = sorted(len(p.tokens) for p in a)
    lb = sorted(len(p.tokens) for p in other)
    assert la[:n // 2] == lb[:n // 2]       # the same quantiles


def test_lengths_keep_to_the_mix_and_arrivals_to_the_window():
    s = sched(3, seconds=40.0)
    assert all(MIX["prompt"]["min"] <= len(p.tokens) <= MIX["prompt"]["max"]
               for p in s)
    assert all(MIX["output"]["min"] <= p.max_new <= MIX["output"]["max"]
               for p in s)
    due = [p.due for p in s]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    assert abs(len(s) - MIX["rate_rps"] * 40.0) <= 0.1 * MIX["rate_rps"] * 40
    med = np.median([len(p.tokens) for p in s])
    assert 0.8 * MIX["prompt"]["median"] <= med <= 1.25 * MIX["prompt"]["median"]
    assert all(1 <= t < 50304 for p in s[:20] for t in p.tokens)


@pytest.mark.parametrize("q,want", [(50, 3), (95, 10), (100, 10), (10, 1)])
def test_percentile_is_of_all_values_by_nearest_rank(q, want):
    values = [3, 1, 2, 5, 4, 10, 3, 3, 2, 1]   # sorted: 1 1 2 2 3 3 3 4 5 10
    assert loadgen.percentile(values, q) == want
