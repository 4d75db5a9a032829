"""The reference and the benchmark's weights, on tiny models (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import manifest
import reference
import weights
from model_spec import from_config


@pytest.mark.parametrize("conf", [bench_tiny.QWEN, bench_tiny.QWEN_UNTIED,
                                  bench_tiny.STARCODER])
def test_served_and_reference_weights_agree(conf):
    m = from_config(conf["name"], conf)
    layout = manifest.family(m.model_type).layout(m)
    key = weights.root_key(2**35 + 1)
    served = weights.served_params(layout, key, layout.served())
    glob, layer = weights.reference_weights(layout, key)
    for n, (name, _, _) in layout.glob.items():
        np.testing.assert_array_equal(np.asarray(served[name], np.float32),
                                      np.asarray(glob[n]))
    (stack,) = layout.stacks
    for l in range(m.layers):
        w = layer(l)
        for n in stack.leaves:
            np.testing.assert_array_equal(
                np.asarray(served["blk/" + n][l], np.float32), np.asarray(w[n]))


def test_weights_refuse_another_layout():
    m = from_config("tiny_qwen", bench_tiny.QWEN)
    layout = manifest.family(m.model_type).layout(m)
    with pytest.raises(ValueError, match="lm_head"):
        weights.served_params(layout, weights.root_key(1),
                              {"lm_head": ((64, 512), "bfloat16")})


@pytest.mark.parametrize("conf", [bench_tiny.QWEN, bench_tiny.QWEN_UNTIED,
                                  bench_tiny.STARCODER])
def test_reference_matches_the_system_prefill(conf):
    """The system's own prefill, fed the benchmark's weights, puts first
    the token the reference puts first (tiny widths, no near-ties)."""
    import dataclasses

    from repro.configs import get_config
    from repro.dist.plan import get_plan
    from repro.models.model import build_model

    m = from_config(conf["name"], conf)
    sysc = conf["system"]
    cfg = dataclasses.replace(get_config(sysc["arch"], smoke=True),
                              **sysc["overrides"])
    model = build_model(cfg, get_plan("serve"))
    key = weights.root_key(5)
    expected = {n: (tuple(s.shape), jnp.dtype(s.dtype).name)
                for n, s in model.param_specs().items()}
    params = weights.served_params(manifest.family(m.model_type).layout(m),
                                   key, expected)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, m.vocab, 37).tolist()
    logits, _ = model.prefill(params, {"tokens": jnp.asarray([prompt])})
    tok = int(jnp.argmax(logits[0, :m.vocab]))
    res = reference.compare(m, key, [{"prompt": prompt, "served": [tok]}])
    assert res[0]["gap"][0] < 0.05
    wrong = (tok + 1) % m.vocab
    res = reference.compare(m, key, [{"prompt": prompt, "served": [wrong]}])
    assert res[0]["gap"][0] > 0.05


def test_fp8_rounding_keeps_scale():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.1
    q = np.asarray(reference.fp8_per_channel(w), np.float32)
    rel = np.abs(q - np.asarray(w)) / (np.abs(np.asarray(w)) + 1e-3)
    assert 0 < np.median(rel) < 0.07
