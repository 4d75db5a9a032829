"""The control, at a size a test run can hold: the reference computed in
fp8 (W8A8), put first where the system's served tokens are, must come out
not correct under a limit that the system's own tokens meet.

The number compared is the mean gap of the served tokens below the
reference's best, in units of the logits' std.  At these tiny widths (2
layers of 64) the system reads 0.00005-0.0007 and the control 0.023-0.053
(CPU, seeds 1-3), so the tiny limit is 0.005; the cell's own limit comes from readings at its own size on the
chip (``limits/*.json``, PERF.md).
"""

import pytest

import bench_tiny

TINY_LIMIT = 0.005


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_system_passes(seed):
    limits = {"gap_mean": {"limit": TINY_LIMIT}, "tokens_compared": 20}
    out = bench_tiny.run(seed=seed, limits=limits, control="w8a8")
    assert out["correct"], out["checks"]
    ctl = out["control"]
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["gap_mean"]["value"] > TINY_LIMIT
    assert ctl["checks"]["gap_mean"]["value"] > 3 * out["checks"]["gap_mean"]["value"]
