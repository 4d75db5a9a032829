"""The sample that ``correct`` is decided on, and the checks' verdicts."""

import numpy as np
import pytest

import correctness
from traffic_common import Request


def finished(idx, n_prompt, n_out):
    r = Request(idx, [1] * n_prompt, n_out - 1, 0.0)
    r.tokens = [2] * n_out
    r.done_t = 1.0
    return r


REQS = [finished(i, 100 + 37 * (i % 7), 8 + (i * 13) % 50) for i in range(40)]


@pytest.mark.parametrize("seed", [0, 5, 2**33 + 1])
def test_sample_is_drawn_from_the_seed_and_holds_the_longest(seed):
    a = correctness.sample(REQS, seed)
    assert [r.idx for r in a] == [r.idx for r in correctness.sample(REQS, seed)]
    longest = max(REQS, key=lambda r: len(r.prompt) + len(r.tokens))
    assert a[0] is longest
    assert len(a) >= correctness.SAMPLE_MIN
    total = sum(len(r.tokens) for r in a)
    assert total >= correctness.SAMPLE_TOKENS or len(a) == correctness.SAMPLE_MAX


def test_unfinished_and_failed_are_not_sampled():
    r = finished(99, 5000, 10)
    r.failed = "boom"
    s = Request(98, [1] * 6000, 3, 0.0)  # never finished
    assert all(x.idx not in (98, 99) for x in correctness.sample(REQS + [r, s], 1))


def test_checks_verdicts():
    limits = {"gap_mean": {"limit": 0.3}, "tokens_compared": 3}
    res = [{"gap": np.array([0.0, 0.1])}, {"gap": np.array([0.5])}]
    ok = correctness.checks(limits, REQS[:2], res, REQS, 0)
    assert all(c["ok"] for c in ok.values())
    assert ok["gap_mean"]["value"] == pytest.approx(0.2)
    bad = correctness.checks(limits, REQS[:2], [{"gap": np.array([0.5] * 6)}],
                             REQS, 1)
    assert not bad["gap_mean"]["ok"] and not bad["unanswered"]["ok"]
    assert not correctness.checks(limits, [], [], REQS, 0)["gap_mean"]["ok"]
    short = finished(100, 10, 5)
    short.tokens = short.tokens[:3]
    assert not correctness.checks(limits, [], res, [short], 0)["wrong_length"]["ok"]
