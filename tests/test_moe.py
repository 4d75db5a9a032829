"""MoE dispatch invariants (the parcel path)."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.configs import get_config
from repro.dist.plan import get_plan
from repro.models.moe import moe_ffn, moe_param_specs
from repro.models.params import init_params

PLAN = get_plan("futurized")


def _layer_params(cfg, rng):
    specs = moe_param_specs(cfg, 1, "")
    p = init_params(specs, rng)
    return {k: v[0] for k, v in p.items()}  # drop the layers dim


def _oracle(cfg, p, xt, lo=0, count=None):
    """Σ_k w_k · expert_k(x) over the experts [lo, lo + count) of the
    routing over all ``n_experts``, computed densely in float32: softmax,
    top k, renormalised only where ``norm_topk_prob``, times
    ``routed_scaling_factor``; no shared experts."""
    count = cfg.n_experts if count is None else count
    probs = jax.nn.softmax(jnp.dot(xt, p["router"], precision="highest"), -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg.routed_scaling_factor
    out = jnp.zeros_like(xt)
    for e in range(lo, lo + count):
        h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_in"][e])
        y = h @ p["w_out"][e]
        out = out + jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None] * y
    return out


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3))
@example(seed=68, B=1)
def test_moe_matches_dense_expert_computation(seed, B):
    """With no drops, the dispatch→GEMM→combine pipeline equals the direct
    per-token mixture Σ_k w_k · expert_k(x) computed densely."""
    cfg = replace(get_config("deepseek_moe_16b", smoke=True),
                  capacity_factor=64.0, n_shared_experts=0)
    rng = jax.random.PRNGKey(seed)
    p = _layer_params(cfg, rng)
    S, D = 8, cfg.d_model
    x = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, D), jnp.float32) * 0.3
    y, aux = moe_ffn(cfg, PLAN, x, p)

    # dense oracle, top-k weights as the config sets them
    mix = _oracle(cfg, p, x.reshape(-1, D))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, D), np.float32),
                               np.asarray(mix, np.float32), atol=5e-2, rtol=5e-2)
    # the serving dispatch computes the same mixture
    ys, _ = moe_ffn(cfg, PLAN, x, p, serve=True)
    np.testing.assert_allclose(np.asarray(ys.reshape(-1, D), np.float32),
                               np.asarray(mix, np.float32), atol=5e-2, rtol=5e-2)
    # E·Σ f_e·P_e ≈ 1 near balance; top-k vs softmax skew keeps it positive
    assert 0.3 < float(aux) < float(cfg.n_experts)


def test_capacity_drops_are_bounded(rng):
    """With cf → 0 the layer must drop (not corrupt) overflow tokens."""
    cfg = replace(get_config("granite_moe_3b_a800m", smoke=True),
                  capacity_factor=1e-6)
    p = _layer_params(cfg, rng)
    x = jax.random.normal(rng, (2, 64, cfg.d_model), jnp.float32)
    y, _ = moe_ffn(cfg, PLAN, x, p)
    assert np.isfinite(np.asarray(y)).all()
    # capacity floor is min(A,16): outputs are not all zero
    assert float(jnp.max(jnp.abs(y))) > 0


def test_shared_experts_always_contribute(rng):
    cfg = replace(get_config("deepseek_moe_16b", smoke=True), capacity_factor=1e-6)
    p = _layer_params(cfg, rng)
    x = jax.random.normal(rng, (1, 4, cfg.d_model), jnp.float32)
    y_with, _ = moe_ffn(cfg, PLAN, x, p)
    p0 = dict(p)
    p0["shared_w_out"] = jnp.zeros_like(p0["shared_w_out"])
    y_without, _ = moe_ffn(cfg, PLAN, x, p0)
    assert float(jnp.max(jnp.abs(y_with - y_without))) > 1e-4


def _f32(arch, **kw):
    """The smoke config computing in float32, so sums over shares compare
    to rounding (1e-5) and not to bf16."""
    return replace(get_config(arch, smoke=True), dtype="float32", **kw)


@pytest.mark.parametrize("S", [1, 12])  # decode's form and prefill's
def test_expert_shares_sum_to_the_uncut_layer(S):
    """Eight layers each told to hold one eighth of the experts
    (``expert_shard=(i, 8)``) route over all of them; their routed parts
    summed, with the shared experts (which every share computes) counted
    once, equal the uncut layer."""
    cfg = _f32("deepseek_v2_lite")
    n = 8
    assert cfg.n_experts % n == 0
    rng = jax.random.PRNGKey(5)
    p = _layer_params(cfg, rng)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (3, S, cfg.d_model)) * 0.5
    whole, _ = moe_ffn(cfg, PLAN, x, p, serve=True)
    no_shared = dict(p, shared_w_out=jnp.zeros_like(p["shared_w_out"]))
    shared = whole - moe_ffn(cfg, PLAN, x, no_shared, serve=True)[0]
    per = cfg.n_experts // n
    total = shared
    for i in range(n):
        ci = replace(cfg, expert_shard=(i, n))
        pi = {k: (v[i * per:(i + 1) * per] if k in ("w_in", "w_gate", "w_out")
                  else v) for k, v in no_shared.items()}
        assert moe_param_specs(ci, 1, "")["w_in"].shape[1] == per
        part, _ = moe_ffn(ci, PLAN, x, pi, serve=True)
        np.testing.assert_allclose(
            np.asarray(part.reshape(-1, cfg.d_model)),
            np.asarray(_oracle(cfg, p, x.reshape(-1, cfg.d_model), i * per, per)),
            rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(whole - shared))) > 1e-2  # routed parts count


@pytest.mark.parametrize("S", [1, 64])
def test_serving_drops_no_token_when_all_route_to_one_expert(S):
    """Every token's largest score is expert 3's: serving computes all of
    them there (capacity dispatch at the same load drops most)."""
    cfg = _f32("deepseek_v2_lite", n_shared_experts=0, capacity_factor=1.0)
    rng = jax.random.PRNGKey(7)
    p = _layer_params(cfg, rng)
    p["router"] = p["router"].at[:, 3].set(5.0)
    x = jnp.abs(jax.random.normal(rng, (64 // S, S, cfg.d_model))) * 0.5
    xt = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xt @ p["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 3))
    want = _oracle(cfg, p, xt)
    y, _ = moe_ffn(cfg, PLAN, x, p, serve=True)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    if S > 1:  # the same load overflows expert 3's capacity when training
        yc, _ = moe_ffn(cfg, PLAN, x, p)
        assert float(jnp.max(jnp.abs(yc.reshape(want.shape) - want))) > 1e-2


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("serve", [False, True])
def test_topk_weights_follow_the_config(norm_topk, serve):
    """``norm_topk_prob`` renormalises the top-k weights or leaves them as
    the softmax gave them; ``routed_scaling_factor`` scales them; the two
    settings give different layers, each the hand-written mixture."""
    outs = []
    for nt in (norm_topk, not norm_topk):
        cfg = _f32("deepseek_v2_lite", n_shared_experts=0, capacity_factor=64.0,
                   norm_topk_prob=nt, routed_scaling_factor=2.5)
        rng = jax.random.PRNGKey(11)
        p = _layer_params(cfg, rng)
        x = jax.random.normal(jax.random.fold_in(rng, 2), (2, 5, cfg.d_model))
        y, _ = moe_ffn(cfg, PLAN, x, p, serve=serve)
        want = _oracle(cfg, p, x.reshape(-1, cfg.d_model))
        np.testing.assert_allclose(np.asarray(y.reshape(want.shape)),
                                   np.asarray(want), rtol=1e-4, atol=1e-5)
        outs.append(y)
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-2
