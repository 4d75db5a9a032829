"""A whole run on the CPU, past the harness's look for a chip, with the
timed path sound and then broken underneath: ``correct`` must follow.

The faults a serving cell can have (``benchmarks/chip/faults.py``): a token
altered where it is produced; a decode step that returns its K/V state
unchanged; half of the batch left out of the step (those rows repeat their
input token); the prefill's K/V lost before they reach the page pool.  One
chip has no exchange between chips to leave out.
"""

import pytest

import bench_tiny
from faults import FAULTS


def test_sound_run_is_correct():
    out = bench_tiny.run()
    assert out["correct"], out["checks"]
    assert out["checks"]["gap_mean"]["value"] < out["checks"]["gap_mean"]["limit"]
    assert list(out)[-1] == "checks"
    assert {"setup_s", "itl_p95_ms", "out_tokens_per_s"} <= set(out["metrics"])
    assert out["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault):
    out = bench_tiny.run(fault=fault)
    assert not out["correct"], out["checks"]


def test_traced_run_reports_host_side_metrics():
    """On the CPU the trace has no device plane: the device metrics are
    left out, the engine's and the cache's are read."""
    out = bench_tiny.run(trace=True)
    assert out["correct"], out["checks"]
    assert {"engine.step_wall_ms", "engine.batch_occupancy",
            "kv_cache.pages_in_use"} <= set(out["metrics"])
    assert not any(k.startswith(("device.", "model_step.")) for k in out["metrics"])
