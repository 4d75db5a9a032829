"""flops.py against hand arithmetic."""

from math import prod

import pytest

import flops
import manifest
from model_spec import from_config

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def spec(name):
    return from_config(name, manifest.config(name))


@pytest.mark.parametrize("name,count", [
    # 36 × 77,076,992 + 151,936 × 2,048 + 2,048: the published 3.09 B
    ("qwen25_3b", 3_085_938_688),
])
def test_tied_parameter_counts(name, count):
    assert flops.param_count(spec(name)) == count


def test_layer_parameters_by_hand():
    q = spec("qwen25_3b")
    (stack,) = manifest.family("qwen2").layout(q).stacks
    assert stack.layers == 36
    assert sum(prod(s) for s, _ in stack.leaves.values()) == (2048 * 2048 * 2 + 2048 * 256 * 2
                                     + 3 * 2048 * 11008 + 2 * 2048
                                     + 2048 + 2 * 256)


def test_decode_step_bytes_at_known_lengths():
    q = spec("qwen25_3b")
    per_token = 36 * 2 * 2 * 128 * 2  # layers × K,V × heads × Dh × bf16
    assert flops.kv_bytes_per_token(q) == per_token == 36864
    c = flops.decode_step(q, [1000, 3000])
    weights = 3_085_938_688 * 2
    assert c["bytes"] == weights + (4000 + 2) * per_token


def test_decode_step_flops_by_hand():
    q = spec("qwen25_3b")
    c = flops.decode_step(q, [99])
    matmul = 36 * (2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008)
    attn = 4 * 36 * 16 * 128 * 100
    assert c["flops"] == 2 * (matmul + 2048 * 151936) + attn


def test_least_time_bound():
    q = spec("qwen25_3b")
    t = flops.decode_least_time(q, [[1024] * 24, [2048] * 24], PEAKS)
    assert t["steps"] == 2 and t["memory_bound_steps"] == 2
    one = flops.decode_step(q, [1024] * 24)["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert t["seconds"] > 2 * one
    assert flops.least_time(197e12, 1.0, PEAKS)["bound"] == "compute"
