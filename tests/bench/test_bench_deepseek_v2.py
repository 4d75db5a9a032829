"""DeepSeek-V2 through the harness: the family module's counts, the system
against the family's float32 reference on seeded random weights (prefill,
then decode through the paged cache manager and the decode program), and
whole tiny runs on the CPU.

The tiny configuration is the system's ``deepseek_v2_lite`` smoke config
(MLA with the published rope dims and YaRN, one dense layer, two MoE
layers) holding share 1 of 2 of its 8 routed experts, so the router's
outputs for the absent experts must add nothing on both sides.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import flops
import manifest
import reference
import weights
from model_spec import from_config

CELL = "deepseek_v2_lite.decode_long"
FAMILY = "deepseek_v2"

TINY = {
    **{k: v for k, v in manifest.config("deepseek_v2_lite").items()
       if k not in ("name", "source", "reduced", "assumed", "departures",
                    "system")},
    "name": "tiny_deepseek_v2", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "v_head_dim": 16, "num_experts_per_tok": 3,
    "n_routed_experts": 4, "vocab_size": 512,
    "expert_parallel": {"router_experts": 8, "chips": 2, "this_chip": 1,
                        "deployment": "two chips share each MoE layer"},
    "system": {"arch": "deepseek_v2_lite", "smoke": True,
               "overrides": {"param_dtype": "bfloat16", "expert_shard": [1, 2]},
               "serve": {"max_batch": 4, "cache_len": 256, "page_size": 16}}}


def test_counts_of_the_configuration():
    """Hand arithmetic of the published model, 8 of 64 experts held."""
    fam = manifest.family(FAMILY)
    conf = manifest.config("deepseek_v2_lite")
    m = from_config("deepseek_v2_lite", conf)
    assert (m.experts, m.held, m.shard) == (64, 8, 0)
    assert flops.kv_bytes_per_token(m) == 27 * 576 * 2 == 31104
    # attention 2048·3072 + 2048·576 + 512 + 512·4096 + 2048·2048 + 2048;
    # dense MLP 3·2048·10944 + 2048; MoE layer: router 2048·64, shared
    # experts 3·2048·2816, held experts 8·3·2048·1408, ln2 2048; embedding,
    # head and final norm 2·102400·2048 + 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048 + 2048
    moe = 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408 + 2048
    held = (27 * attn + 3 * 2048 * 10944 + 2048 + 26 * moe
            + 2 * 102400 * 2048 + 2048)
    assert flops.param_count(m) == held == 3110989312
    whole = dict(conf, n_routed_experts=64,
                 expert_parallel={**conf["expert_parallel"], "chips": 1})
    assert flops.param_count(from_config("whole", whole)) == 15706484224
    # absorbed decode attention, per query per key: 2·16·(576 + 512)·27
    assert flops._attn_flops(m, 1, 1) == 2 * 16 * (576 + 512) * 27
    # held experts some of 24 rows route to, under even routing
    routed = 8 * (1 - (58 / 64) ** 24)
    step = fam.expert_step(m, 24)
    assert step["bytes"] == pytest.approx(routed * 3 * 2048 * 1408 * 26 * 2)
    assert step["flops"] == pytest.approx(2 * 24 * 6 * 8 / 64 * 3 * 2048 * 1408 * 26)
    d = flops.decode_step(m, [1000] * 24)
    assert d["bytes"] == pytest.approx(
        (held - 26 * 8 * 3 * 2048 * 1408) * 2 + 24 * 1001 * 31104 + step["bytes"])
    # the routed experts' bytes are below what reading all 8 takes, so no
    # share computed from these counts can pass 100%
    assert routed < 8


def test_yarn_frequencies_of_the_reference():
    """The reference's YaRN ramp, from the closed form (low 10, high 23)."""
    m = from_config("deepseek_v2_lite", manifest.config("deepseek_v2_lite"))
    fam = manifest.family(FAMILY)
    i = np.arange(32)
    base = 1e4 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(fam.inv_freq(m), base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-12)
    assert fam.softmax_scale(m) == pytest.approx(0.114721, abs=1e-6)


def _system(conf):
    from repro.configs import get_config
    from repro.dist.plan import get_plan
    from repro.models.model import build_model

    sysc = conf["system"]
    cfg = dataclasses.replace(get_config(sysc["arch"], smoke=True),
                              **sysc["overrides"])
    return cfg, build_model(cfg, get_plan("serve"))


def _reference_logits(m, key, tokens):
    """The family's float32 forward pass, every position's logits."""
    fam = manifest.family(FAMILY)
    layout = fam.layout(m)
    glob, layer_w = weights.reference_weights(layout, key)
    mm = partial(reference._matmul, dt=jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = glob["embed"][jnp.asarray(tokens)]
        for st in layout.stacks:
            for layer in range(st.first, st.first + st.layers):
                x = fam.layer(m, st.prefix, jnp.float32, layer_w(layer), x, mm)
        x = fam.final(m, glob, x)
        return np.asarray(x @ fam.logits_table(m, glob)[:m.vocab].T)


def _served_logits(conf, key, prompt, steps):
    """The system's logits after the first ``len(prompt) - steps + 1``
    tokens of ``prompt`` (its prefill, right-padded to 64), then after each
    further token fed to ``steps - 1`` decode steps: through the cache
    manager (``PagedKVCache.admit`` scatters the prefill's latent rows into
    pages) and the jitted paged decode program, beside an idle row."""
    from repro.serve.kv_cache import PagedKVCache

    m = from_config(conf["name"], conf)
    cfg, model = _system(conf)
    manifest.family(FAMILY).check_system(cfg, m, 256)
    expected = {n: (tuple(s.shape), jnp.dtype(s.dtype).name)
                for n, s in model.param_specs().items()}
    params = weights.served_params(manifest.family(FAMILY).layout(m), key,
                                   expected)
    kv = PagedKVCache(model, num_pages=12, page_size=16, max_batch=2,
                      max_pages_per_req=8, name="dsv2_test")
    assert set(kv.pools) == {"ckv", "ckv0"}
    n = len(prompt) - steps + 1
    toks = np.zeros((1, 64), np.int32)
    toks[0, :n] = prompt[:n]
    logits, cache = jax.jit(model.prefill, static_argnames=("cache_len",))(
        params, {"tokens": jnp.asarray(toks)}, cache_len=64,
        valid_len=jnp.asarray([n], jnp.int32))
    out = [np.asarray(logits[0, :m.vocab])]
    assert kv.admit(1, cache, n)
    step = jax.jit(model.decode_paged, donate_argnums=(1,))
    for t in range(steps - 1):
        assert kv.ensure_next_token(1)
        tok = np.zeros((2, 1), np.int32)
        tok[1, 0] = prompt[n + t]
        logits, new = step(params, kv.device_cache(), jnp.asarray(tok))
        out.append(np.asarray(logits[1, :m.vocab]))
        # after the step has read them: on the CPU the device arrays may
        # alias the host's page table and positions
        kv.update_pools(new)
        kv.pos[1] += 1
    return np.stack(out)


def _gaps(served, ref):
    """Per position, the largest logit difference over the reference
    logits' std."""
    return np.max(np.abs(served - ref), -1) / ref.std(-1)


def test_system_agrees_with_the_reference_by_logits():
    """Prefill of 37 tokens, then 12 decode steps through the paged latent
    pool, against the reference's full forward pass over the same 49
    tokens: 13 positions.  The system computes in bf16, the reference in
    float32: the largest logit difference is bf16 rounding through 3
    layers, 0.033-0.065 of the reference logits' std at these positions.
    Tolerance 0.1 of the std at every position: RoPE without the YaRN ramp
    puts every position at 0.15-0.94.  (Scores rounded to bf16 move these
    random-weight logits by less than bf16's own noise, 0.095 at most;
    ``tests/test_mla.py`` holds the scores' precision where they are
    large.)"""
    key = weights.root_key(2**33 + 17)
    prompt = np.random.default_rng(5).integers(1, 512, 49).tolist()
    m = from_config(TINY["name"], TINY)
    served = _served_logits(TINY, key, prompt, 13)
    ref = _reference_logits(m, key, prompt)[36:]
    assert served.shape == ref.shape == (13, 512)
    gaps = _gaps(served, ref)
    assert gaps.max() < 0.1, gaps


def test_tiny_run_is_correct():
    """A whole tiny run of the cell's harness: prefill, paged latent
    decode, held experts, and the reference that decides ``correct``."""
    out = bench_tiny.run(conf=TINY, limits={**manifest.limits(CELL),
                                            "tokens_compared": 20})
    assert out["correct"], out["checks"]
    assert out["checks"]["gap_mean"]["value"] < out["checks"]["gap_mean"]["limit"]
