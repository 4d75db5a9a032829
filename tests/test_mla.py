"""Multi-head latent attention (DeepSeek-V2): YaRN RoPE, the absorbed decode
form against the plain one, and the latent page pool."""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.plan import get_plan
from repro.models import layers as Lx
from repro.models import transformer
from repro.models.model import build_model

PLAN = get_plan("serve")


def _f32(**kw):
    """The smoke config in float32 at ``highest`` precision: the absorbed
    and the plain forms then differ only in the order of float32 sums."""
    return replace(get_config("deepseek_v2_lite", smoke=True),
                   dtype="float32", param_dtype="float32", **kw)


def test_yarn_inv_freq_is_the_closed_form():
    """64 rope dims, base 1e4, factor 40 over 4096 positions, beta_fast 32,
    beta_slow 1: the ramp runs from dim 10 to dim 23 (floor and ceil of the
    correction dims); below it the plain frequency, above it that over 40."""
    cfg = get_config("deepseek_v2_lite")
    dim = cfg.qk_rope_head_dim
    corr = [dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(1e4))
            for r in (32, 1)]
    assert (math.floor(corr[0]), math.ceil(corr[1])) == (10, 23)
    got = np.asarray(Lx.rope_inv_freq(cfg, dim), np.float64)
    i = np.arange(dim // 2)
    base = 1e4 ** (-2.0 * i / dim)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[9] == pytest.approx(base[9], rel=1e-6)
    assert got[23] == pytest.approx(base[23] / 40, rel=1e-6)
    # the plain rotary frequencies without YaRN
    np.testing.assert_allclose(
        np.asarray(Lx.rope_inv_freq(replace(cfg, yarn_factor=0.0), dim)),
        base, rtol=1e-6)


def test_softmax_scale_and_rope_of_the_published_config():
    cfg = get_config("deepseek_v2_lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert Lx.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert Lx.mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    # de-interleave then rotate half: at position 0 the dims are only
    # reordered (x0, x2, ..., x1, x3, ...)
    x = jnp.arange(64, dtype=jnp.float32).reshape(1, 1, 64)
    y = Lx.mla_rope(cfg, x, jnp.zeros((1,), jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(y[0, 0]), np.concatenate([np.arange(0, 64, 2),
                                             np.arange(1, 64, 2)]))


def _params(cfg, seed=0):
    return build_model(cfg, PLAN).init(jax.random.PRNGKey(seed))


def test_absorbed_decode_equals_the_plain_form():
    """One query at position S against the latent rows of S+1 tokens, in
    absorbed form (``mla_decode_attention``: ``q_nope W_UK^T`` against
    c_kv, ``W_UV`` after the softmax), equals the plain form's last row
    (``mla_attention``: k_nope ‖ v = c_kv W_kv_b per head)."""
    cfg = _f32()
    lp = {k[len("blk/"):]: v[0] for k, v in _params(cfg).items()
          if k.startswith("blk/")}
    B, S, D = 2, 21, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S + 1, D))
    with jax.default_matmul_precision("highest"):
        plain, (lat,) = Lx.mla_attention(cfg, PLAN, x, lp, "",
                                         jnp.arange(S + 1), return_kv=True)
        cache = jnp.zeros((B, S + 8, 1, lat.shape[-1])).at[:, :S].set(lat[:, :S])
        pos = jnp.full((B,), S, jnp.int32)
        got, cache = Lx.mla_decode_attention(cfg, PLAN, x[:, S:], lp, "",
                                             cache, pos)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(plain[:, S]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache[:, S]), np.asarray(lat[:, S]),
                               rtol=1e-6, atol=1e-6)


def test_paged_latent_decode_matches_the_forward_pass():
    """Prefill, then three steps of the paged decode program against one
    latent pool per layer group (K/V pools absent), equal the full forward
    pass's logits at float32.  Row 1 is idle on scratch page 0."""
    cfg = _f32()
    model = build_model(cfg, PLAN)
    params = _params(cfg, 3)
    page, maxp, B = 8, 4, 2
    P = maxp + 2
    specs = model.paged_cache_specs(P, page, B, maxp)
    assert {k for k in specs if k not in ("page_table", "pos")} == {"ckv", "ckv0"}
    assert specs["ckv"].shape == (cfg.num_layers - 1, P, 1, page, 128)  # 32 + 64 padded
    S, steps = 13, 3
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, S + steps), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        _, pre = model.prefill(params, {"tokens": toks[:, :S]}, cache_len=16)
        pages = np.asarray([3, 1, 5, 0], np.int32)
        cache = {}
        for key in ("ckv", "ckv0"):
            pool = jnp.zeros(specs[key].shape)
            rows = pre[key][:, 0].reshape(-1, 2, page, 1, 128)
            cache[key] = pool.at[:, pages[:2]].set(rows.transpose(0, 1, 3, 2, 4))
        cache["page_table"] = jnp.asarray([pages, np.zeros(maxp, np.int32)])
        cache["pos"] = jnp.asarray([S, 0], jnp.int32)
        step = jax.jit(model.decode_paged)
        for t in range(steps):
            tok = jnp.stack([toks[0, S + t:S + t + 1], jnp.zeros((1,), jnp.int32)])
            logits, cache = step(params, cache, tok)
            full = transformer.forward(cfg, PLAN, params,
                                       toks[:, :S + t + 1])[0][0, -1]
            np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full),
                                       rtol=1e-4, atol=1e-4)
            cache = dict(cache, pos=cache["pos"].at[1].set(0))


def test_absorbed_scores_keep_float32_where_scores_are_large():
    """Scores of trained attention run to tens of units (a key aligned with
    its query); there a score rounded to bf16 is off by up to 1/8, which
    moves the softmax weights by about a tenth.  Here each latent row's
    rope part is its query's, scaled by 0.5-1.5, so the scores run from
    about 20 to 60; the system's bf16 decode (bf16 inputs, float32 scores
    and softmax) must stay within 1% of the float32 plain form, as it does
    to about 0.5%.  Scores rounded to bf16 miss by about 9%."""
    cfg = replace(get_config("deepseek_v2_lite", smoke=True))
    H, Dn, Dr, R, Dv = 4, 16, 64, 32, 16
    B, T = 2, 96
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    bf = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    wkv_b = bf(jax.random.normal(k[0], (R, H * (Dn + Dv))) / np.sqrt(R))
    q_nope = bf(jax.random.normal(k[1], (B, H, Dn)))
    q_pe = bf(jax.random.normal(k[2], (B, H, Dr)) * 1.6)
    c = bf(jax.random.normal(k[3], (B, T, R)) * 0.3)
    f = jax.random.uniform(k[4], (B, T, 1), minval=0.5, maxval=1.5)
    lat = bf(jnp.concatenate([c, q_pe[:, :1] * f], -1))  # head 0's query
    valid = jnp.arange(T)[None, :] < jnp.asarray([[T], [T - 17]])
    got = Lx._mla_absorbed(cfg, {"wkv_b": wkv_b}, "", q_nope, q_pe, lat, valid)

    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        kv = (f32(lat[..., :R]) @ f32(wkv_b)).reshape(B, T, H, Dn + Dv)
        s = (jnp.einsum("bhn,bthn->bht", f32(q_nope), kv[..., :Dn])
             + jnp.einsum("bhr,btr->bht", f32(q_pe), f32(lat[..., R:])))
        s = s * Lx.mla_softmax_scale(cfg)
        assert float(jnp.max(jnp.where(valid[:, None], s, 0))) > 40
        p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), -1)
        want = jnp.einsum("bht,bthv->bhv", p, kv[..., Dn:]).reshape(B, 1, H * Dv)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    assert err < 0.01, err
