"""The traffic generator: a seed fixes the requests; every seed gets the
same lengths in the same order; lengths stay in their ranges."""

import numpy as np
import pytest

import manifest
from traffic_common import stratified_order

MIXES = ["decode_long"]
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]
SECONDS = 30


def generate(mix_name, seed, seconds=SECONDS, vocab=49152):
    mix = manifest.traffic(mix_name)
    return manifest.generator(mix["kind"]).generate(mix, seed, seconds, vocab)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(mix, seed):
    a, b = generate(mix, seed), generate(mix, seed)
    assert [(r.prompt, r.max_new, r.send_at) for r in a] == \
           [(r.prompt, r.max_new, r.send_at) for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_not_in_sizes(mix):
    """Seeds draw the token ids; the lengths and their order, and so the
    work of every window, are the same for every seed."""
    runs = [generate(mix, s) for s in SEEDS]
    schedule = [[(len(r.prompt), r.max_new) for r in rs] for rs in runs]
    assert all(s == schedule[0] for s in schedule)
    assert len(set(schedule[0])) > len(schedule[0]) // 2  # lengths vary
    assert runs[0][0].prompt != runs[1][0].prompt


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_lengths_inside_ranges(mix, seed):
    m = manifest.traffic(mix)
    for r in generate(mix, seed):
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new + 1 <= m["output"]["max"]
        assert all(1 <= t < 49152 for t in r.prompt)


def test_lognormal_medians():
    for mix in MIXES:
        m = manifest.traffic(mix)
        rs = generate(mix, 1)
        assert abs(np.median([len(r.prompt) for r in rs])
                   / m["prompt"]["median"] - 1) < 0.05
        assert abs(np.median([r.max_new + 1 for r in rs])
                   / m["output"]["median"] - 1) < 0.08


def test_backlog_outlasts_the_window():
    m = manifest.traffic("decode_long")
    rs = generate("decode_long", 3)
    assert len(rs) == m["requests"] >= (2 * m["block"] + m["min_queued"]
                                        + m["open_after_completed"])
    assert all(r.send_at == 0.0 for r in rs)


@pytest.mark.parametrize("n,block", [(480, 24), (90, 16), (7, 16), (100, 1)])
def test_stratified_order_is_a_balanced_permutation(n, block):
    order = stratified_order(n, block, np.random.default_rng(5))
    assert sorted(order) == list(range(n))
    b = min(block, n)
    for start in range(0, n - n % b, b):
        strata = sorted((i * b) // n for i in order[start:start + b])
        assert strata == list(range(b))
