"""Shared model primitives: norms, RoPE, GQA attention (full / windowed /
decode), gated MLPs, embeddings, cross-entropy.

All functions are mesh-agnostic: activations are constrained through the
:class:`~repro.dist.plan.ShardingPlan` by *logical* axes, weights carry
their own sharding — GSPMD derives the TP collectives.  Compute dtype is
``cfg.dtype`` (bf16), softmax/logits/loss accumulate in fp32.

Long-context note: attention uses an exact query-chunked formulation
(outer loop over Q blocks via ``lax.scan``) once ``S > _CHUNK_THRESHOLD``,
bounding the live score buffer to (B, H, chunk, T) — the XLA analogue of
the flash-attention outer loop (the inner online-softmax lives in the
Pallas kernel, ``kernels/flash_attention.py``).  Sliding-window attention
is banded: each Q block attends to a static (window + chunk) K/V slice, so
windowed prefill is O(S·w), which is what makes the hybrid arch's 500k
cell sub-quadratic.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.plan import ShardingPlan

_CHUNK_THRESHOLD = 2048  # S above this → Q-chunked attention (bounded scores)
_Q_CHUNK = 1024


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------------- norms
def norm(cfg: ModelConfig, x: jax.Array, scale: jax.Array,
         bias: Optional[jax.Array] = None) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------- bf16 grad boundary
@jax.custom_vjp
def bf16_cotangent(x: jax.Array) -> jax.Array:
    """Identity forward; backward casts the cotangent to bf16 (and back).

    Placed after the fp32 softmax/score region of attention so the dq/dk/dv
    cotangents — and therefore the per-layer dx all-reduces over the model
    axis — ride the wire at half width (EXPERIMENTS.md §Perf, granite_34b).
    """
    return x


def _bf16_ct_fwd(x):
    return x, None


def _bf16_ct_bwd(_, ct):
    return (ct.astype(jnp.bfloat16).astype(ct.dtype),)


bf16_cotangent.defvjp(_bf16_ct_fwd, _bf16_ct_bwd)


# -------------------------------------------------------------------- rope
def rope_tables(cfg: ModelConfig, positions: jax.Array, head_dim: int) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int32 → cos/sin tables (..., head_dim/2) fp32."""
    half = head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2). NeoX rotate-half."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) → broadcast over batch & heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# -------------------------------------------------------------- activations
def act_fn(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)


# --------------------------------------------------------------------- mlp
def mlp(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array, p: Dict[str, jax.Array],
        prefix: str) -> jax.Array:
    """Gated (SwiGLU/GeGLU) or plain 2-layer MLP. Weights: w_in/w_gate/w_out."""
    dt = cdtype(cfg)
    with jax.named_scope("mlp"):
        h = x @ p[f"{prefix}w_in"].astype(dt)
        if cfg.glu:
            g = x @ p[f"{prefix}w_gate"].astype(dt)
            h = act_fn(cfg, g) * h
        else:
            h = act_fn(cfg, h)
        return h @ p[f"{prefix}w_out"].astype(dt)


# --------------------------------------------------------------- attention
def _qkv(cfg: ModelConfig, x: jax.Array, p: Dict[str, jax.Array], prefix: str):
    dt = cdtype(cfg)
    B, S, _ = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p[f"{prefix}wq"].astype(dt)
    k = x @ p[f"{prefix}wk"].astype(dt)
    v = x @ p[f"{prefix}wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p[f"{prefix}bq"].astype(dt)
        k = k + p[f"{prefix}bk"].astype(dt)
        v = v + p[f"{prefix}bv"].astype(dt)
    return (
        q.reshape(B, S, H, Dh),
        k.reshape(B, S, KV, Dh),
        v.reshape(B, S, KV, Dh),
    )


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array],
          scale: float) -> jax.Array:
    """q: (B,Sq,KV,G,Dh), k/v: (B,T,KV,Dh), mask: (Sq,T) additive fp32."""
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = s + mask
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o


def _causal_mask(sq: int, t: int, q_start, window: int = 0) -> jax.Array:
    """Additive mask (sq, t): causal, optionally banded to `window`."""
    qpos = q_start + jnp.arange(sq)[:, None]
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if window > 0:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
              p: Dict[str, jax.Array], prefix: str, positions: jax.Array,
              causal: bool = True, window: int = 0, return_kv: bool = False):
    """Self-attention over full sequences (train / prefill path).

    With ``return_kv=True`` also returns the (post-RoPE) K/V used — the
    prefill path collects them into the cache in the same pass.
    """
    dt = cdtype(cfg)
    B, S, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    with jax.named_scope("qkv"):
        q, k, v = _qkv(cfg, x, p, prefix)
        if cfg.rope:
            cos, sin = rope_tables(cfg, positions, Dh)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    if getattr(plan, "bf16_boundaries", False):
        q, k, v = bf16_cotangent(q), bf16_cotangent(k), bf16_cotangent(v)
    q = plan.constrain(q.reshape(B, S, KV, G, Dh), ("batch", "seq", None, None, None))
    k = plan.constrain(k, ("batch", "seq", None, None))
    v = plan.constrain(v, ("batch", "seq", None, None))
    scale = 1.0 / math.sqrt(Dh)

    if cfg.attn_impl == "pallas":  # flash kernel path (single source, P7)
        from repro.kernels import ops as kops

        o = kops.flash_attention_trainable(
            q.reshape(B, S, KV, G, Dh).reshape(B, S, H, Dh), k, v,
            causal, window).reshape(B, S, KV, G, Dh)
    elif S <= _CHUNK_THRESHOLD and window == 0:
        mask = _causal_mask(S, S, 0) if causal else None
        o = _sdpa(q, k, v, mask, scale)
    elif window > 0 and causal:
        o = _banded_attention(q, k, v, scale, window)
    else:
        o = _chunked_attention(q, k, v, scale, causal)
    o = o.reshape(B, S, H * Dh)
    out = o @ p[f"{prefix}wo"].astype(dt)
    if return_kv:
        return out, (k, v)
    return out


def _chunked_attention(q, k, v, scale, causal) -> jax.Array:
    """Exact attention, outer loop over Q chunks (bounds score memory)."""
    B, S, KV, G, Dh = q.shape
    C = _Q_CHUNK
    nc = S // C
    assert S % C == 0, f"seq {S} not divisible by q-chunk {C}"
    qc = q.reshape(B, nc, C, KV, G, Dh).transpose(1, 0, 2, 3, 4, 5)  # (nc,B,C,KV,G,Dh)

    def body(_, args):
        i, qi = args
        mask = _causal_mask(C, S, i * C) if causal else None
        return None, _sdpa(qi, k, v, mask, scale)

    _, oc = jax.lax.scan(body, None, (jnp.arange(nc), qc))
    return oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, v.shape[-1])


def _banded_attention(q, k, v, scale, window) -> jax.Array:
    """Sliding-window attention, O(S·window): each Q chunk sees a static
    (window + chunk) K/V slice."""
    B, S, KV, G, Dh = q.shape
    C = min(_Q_CHUNK, S)
    if S % C != 0:
        C = S  # tiny sequences: single chunk
    nc = S // C
    W = min(window, S)
    span = W + C  # kv slice length per chunk
    # pad kv on the left so the slice window never underflows
    kp = jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0)))
    qc = q.reshape(B, nc, C, KV, G, Dh).transpose(1, 0, 2, 3, 4, 5)

    def body(_, args):
        i, qi = args
        start = i * C  # in padded coords the usable span starts here
        ks = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        # positions: q rows are start..start+C-1 (unpadded); kv cols map to
        # unpadded positions start-W..start+C-1
        qpos = jnp.arange(C)[:, None] + start
        kpos = jnp.arange(span)[None, :] + start - W
        ok = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
        return None, _sdpa(qi, ks, vs, mask, scale)

    _, oc = jax.lax.scan(body, None, (jnp.arange(nc), qc))
    return oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, Dh)


# ------------------------------------------------------------ decode attn
def _rope_single(cfg: ModelConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    """RoPE for one position per batch row. x: (B, h, Dh), pos: (B,)."""
    cos, sin = rope_tables(cfg, pos, x.shape[-1])  # (B, half)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def decode_attention(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                     p: Dict[str, jax.Array], prefix: str,
                     k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array,
                     window: int = 0,
                     cross: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against a KV cache, per-slot positions.

    x: (B, 1, D); k_cache/v_cache: (B, T, KV, Dh); pos: (B,) current index
    per batch slot (continuous batching: slots advance independently).
    Returns (out (B,1,D), new_k, new_v).  With the ``optimized`` plan the
    cache is sequence-sharded over the model axis and GSPMD emits the
    flash-decoding partial-softmax combine.  ``cross=True`` skips the cache
    update and attends to the full (encoder) cache.
    """
    dt = cdtype(cfg)
    B, _, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    T = k_cache.shape[1]
    q = x @ p[f"{prefix}wq"].astype(dt)
    if cfg.qkv_bias:
        q = q + p[f"{prefix}bq"].astype(dt)
    q = q.reshape(B, KV * G, Dh)
    if not cross:
        k = x @ p[f"{prefix}wk"].astype(dt)
        v = x @ p[f"{prefix}wv"].astype(dt)
        if cfg.qkv_bias:
            k = k + p[f"{prefix}bk"].astype(dt)
            v = v + p[f"{prefix}bv"].astype(dt)
        k = k.reshape(B, KV, Dh)
        v = v.reshape(B, KV, Dh)
        if cfg.rope:
            q = _rope_single(cfg, q, pos)
            k = _rope_single(cfg, k, pos)
        # ring-buffer slot for windowed caches, plain append otherwise
        slot = jnp.mod(pos, T) if window > 0 else jnp.minimum(pos, T - 1)
        k_cache = k_cache.at[jnp.arange(B), slot].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[jnp.arange(B), slot].set(v.astype(v_cache.dtype))
    else:
        if cfg.rope:
            q = _rope_single(cfg, q, pos)

    q = q.reshape(B, KV, G, Dh)
    kc = plan.constrain(k_cache, ("batch", "kv_seq", None, None))
    vc = plan.constrain(v_cache, ("batch", "kv_seq", None, None))
    s = jnp.einsum("bkgd,btkd->bkgt", q, kc.astype(dt),
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    idx = jnp.arange(T)[None, :]
    if cross:
        valid = jnp.ones((B, T), bool)
    elif window > 0:  # ring buffer: everything valid once wrapped
        valid = (idx <= jnp.mod(pos, T)[:, None]) | (pos >= T)[:, None]
    else:
        valid = idx <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", pr.astype(dt), vc.astype(dt))
    o = o.reshape(B, 1, H * Dh)
    return o @ p[f"{prefix}wo"].astype(dt), k_cache, v_cache


# ------------------------------------------------------- paged decode attn
def paged_decode_attention(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                           p: Dict[str, jax.Array], prefix: str,
                           k_pool: jax.Array, v_pool: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           pos: jax.Array
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against a *paged* KV cache, read in place.

    x: (B, 1, D); k_pool/v_pool: (L, P, KV, page, Dh) stacked block pools
    shared by all requests (head-major pages: the kernel's tiling wants
    (page, Dh) last); layer: () int32, this layer's index into them;
    page_table: (B, maxp) int32 (per-request page lists, 0-padded past the
    fill — page 0 is the pool's reserved scratch page); pos: (B,) current
    fill per slot.  The pools are not written: returns (out (B,1,D), k, v)
    with the new token's k, v ``(B, KV, Dh)`` in the pools' dtype, which
    the caller writes for every layer at once after its layer scan
    (:func:`write_paged_rows`).  Attention sees the new token at ``pos``
    all the same: :func:`repro.kernels.ops.paged_attention` takes it as an
    input beside the pools (on TPU the Pallas kernel, which reads each
    row's live pages only; elsewhere the XLA gather of every row's page
    list).

    Named scopes, which the profiler's op names carry: ``qkv`` (the
    projections and RoPE) and ``paged_attention`` (the page walk, masking,
    softmax and PV).
    """
    dt = cdtype(cfg)
    B, _, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q = x @ p[f"{prefix}wq"].astype(dt)
        k = x @ p[f"{prefix}wk"].astype(dt)
        v = x @ p[f"{prefix}wv"].astype(dt)
        if cfg.qkv_bias:
            q = q + p[f"{prefix}bq"].astype(dt)
            k = k + p[f"{prefix}bk"].astype(dt)
            v = v + p[f"{prefix}bv"].astype(dt)
        q = q.reshape(B, H, Dh)
        k = k.reshape(B, KV, Dh)
        v = v.reshape(B, KV, Dh)
        if cfg.rope:
            q = _rope_single(cfg, q, pos)
            k = _rope_single(cfg, k, pos)
    k = k.astype(k_pool.dtype)
    v = v.astype(v_pool.dtype)

    from repro.kernels import ops as kops

    with jax.named_scope("paged_attention"):
        o = kops.paged_attention(q.reshape(B, KV, H // KV, Dh),
                                 (k_pool, v_pool), (k, v), layer, page_table,
                                 pos, scale=1.0 / math.sqrt(Dh),
                                 value_width=Dh)
        o = o.reshape(B, 1, H * Dh)
    return o @ p[f"{prefix}wo"].astype(dt), k, v


def write_paged_rows(pool: jax.Array, rows: jax.Array, page_table: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """Write one decode step's new rows for every layer into a stacked pool,
    under the named scope ``kv_write``.

    pool: (L, P, KV, page, Dh); rows: (L, B, KV, Dh), as the paged decode
    attentions return them per layer.  Row b's token goes to page
    ``page_table[b, pos[b] // page]`` at offset ``pos[b] % page``; idle
    slots all hit scratch page 0.  Each (layer, row, KV head) writes one
    ``(Dh,)`` row, which keeps the pool's (page, Dh) tiling: a ``(KV, Dh)``
    update window cuts across it, and XLA then lays the whole pool out again
    and back.  In place when the pool is donated.
    """
    L, _, KV, page, _ = pool.shape
    B = pos.shape[0]
    with jax.named_scope("kv_write"):
        pidx = page_table[jnp.arange(B), pos // page]
        idx = (jnp.arange(L)[:, None, None], pidx[None, :, None],
               jnp.arange(KV)[None, None, :], (pos % page)[None, :, None])
        return pool.at[idx].set(rows.astype(pool.dtype))


# ------------------------------------------------- multi-head latent attention
def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_correction_dim(cfg: ModelConfig, rotations: float, dim: int) -> float:
    """The rotary dim whose wavelength turns ``rotations`` times over the
    original context."""
    return (dim * math.log(cfg.yarn_original_max_position
                           / (rotations * 2 * math.pi))
            / (2 * math.log(cfg.rope_theta)))


def rope_inv_freq(cfg: ModelConfig, dim: int) -> jax.Array:
    """Inverse frequencies (dim/2,) fp32 of ``dim`` rotary dims:
    ``rope_theta^(-2i/dim)``, or with YaRN (``yarn_factor`` > 1)
    DeepSeek-V2's blend of that and that over the factor, by a linear ramp
    from ``floor`` of the correction dim of ``yarn_beta_fast`` to ``ceil``
    of that of ``yarn_beta_slow`` (dims below the ramp keep their frequency,
    dims above it are interpolated)."""
    extra = cfg.rope_theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.yarn_factor <= 1:
        return extra
    low = max(math.floor(_yarn_correction_dim(cfg, cfg.yarn_beta_fast, dim)), 0)
    high = min(math.ceil(_yarn_correction_dim(cfg, cfg.yarn_beta_slow, dim)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / cfg.yarn_factor * ramp + extra * (1.0 - ramp)


def mla_rope(cfg: ModelConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    """DeepSeek-V2's RoPE of the rope dims. x: (..., h, Dr); pos: x's
    leading dims without the head dim, or a suffix of them ((S,) against
    (B, S, h, Dr)).  The dims are de-interleaved first (``view(Dr/2, 2)``
    transposed), then rotated half against half; cos/sin carry YaRN's
    ``mscale / mscale_all_dim``."""
    Dr = x.shape[-1]
    half = Dr // 2
    ang = pos.astype(jnp.float32)[..., None] * rope_inv_freq(cfg, Dr)
    m = 1.0
    if cfg.yarn_factor > 1:
        m = (yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    c, s = (jnp.cos(ang) * m)[..., None, :], (jnp.sin(ang) * m)[..., None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    xf = jnp.swapaxes(xf, -1, -2).reshape(x.shape)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's ``mscale_all_dim`` factor
    squared where the model scales its rotary embedding."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        m = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s *= m * m
    return s


def mla_row_width(cfg: ModelConfig) -> int:
    """Width of one cached latent row: c_kv ‖ k_pe (R + Dr), zero-padded to
    a multiple of 128 lanes.  The chip pads a 576-wide row to 640 all the
    same, but with the padding implicit its default layout for the pool puts
    the page index in the lanes, and the decode program then copies the
    whole pool into a row-major layout and back every step."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _mla_project(cfg: ModelConfig, x: jax.Array, p: Dict[str, jax.Array],
                 prefix: str, pos: jax.Array):
    """x: (..., D) → q_nope (..., H, Dn), q_pe (..., H, Dr) after RoPE, and
    the latent row (..., :func:`mla_row_width`): RMSNorm(c_kv) ‖ RoPE(k_pe)
    ‖ zeros, which is all the cache holds of a token."""
    dt = cdtype(cfg)
    H, Dn, Dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    R = cfg.kv_lora_rank
    q = (x @ p[f"{prefix}wq"].astype(dt)).reshape(*x.shape[:-1], H, Dn + Dr)
    a = x @ p[f"{prefix}wkv_a"].astype(dt)
    c = norm(cfg, a[..., :R], p[f"{prefix}kv_norm"])
    k_pe = mla_rope(cfg, a[..., None, R:], pos)[..., 0, :]
    pad = jnp.zeros((*c.shape[:-1], mla_row_width(cfg) - R - Dr), c.dtype)
    return (q[..., :Dn], mla_rope(cfg, q[..., Dn:], pos),
            jnp.concatenate([c, k_pe.astype(c.dtype), pad], axis=-1))


def mla_attention(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                  p: Dict[str, jax.Array], prefix: str, positions: jax.Array,
                  return_kv: bool = False):
    """Causal multi-head latent attention over full sequences (train /
    prefill), as DeepSeek-V2 writes it: ``k_nope ‖ v = c_kv W_kv_b`` per
    head, the one ``k_pe`` shared by every head.  With ``return_kv=True``
    also returns ``(latent,)``: the (B, S, 1, :func:`mla_row_width`) rows
    the cache holds.
    """
    dt = cdtype(cfg)
    B, S, _ = x.shape
    H, Dn, Dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    R, Dv = cfg.kv_lora_rank, cfg.v_head_dim
    with jax.named_scope("qkv"):
        q_nope, q_pe, lat = _mla_project(cfg, x, p, prefix, positions)
        kv = (lat[..., :R] @ p[f"{prefix}wkv_b"].astype(dt)).reshape(
            B, S, H, Dn + Dv)
        k_pe = jnp.broadcast_to(lat[:, :, None, R:R + Dr], (B, S, H, Dr))
        k = jnp.concatenate([kv[..., :Dn], k_pe], axis=-1)
        v = kv[..., Dn:]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
    q = plan.constrain(q[:, :, :, None, :], ("batch", "seq", None, None, None))
    scale = mla_softmax_scale(cfg)
    if S <= _CHUNK_THRESHOLD:
        o = _sdpa(q, k, v, _causal_mask(S, S, 0), scale)
    else:
        o = _chunked_attention(q, k, v, scale, True)
    out = o.reshape(B, S, H * Dv) @ p[f"{prefix}wo"].astype(dt)
    if return_kv:
        return out, (lat[:, :, None, :],)
    return out


def _mla_query(cfg: ModelConfig, p: Dict[str, jax.Array], prefix: str,
               q_nope: jax.Array, q_pe: jax.Array, width: int) -> jax.Array:
    """The absorbed query (B, H, width): ``W_kv_b``'s key part folds into
    the query (``q_lat = q_nope W_UK^T``, scope ``mla_absorb``), then
    ``[q_lat ‖ q_pe ‖ 0]``, so a score is that dotted with a latent row —
    one key shared by the heads."""
    dt = cdtype(cfg)
    B, H, Dn = q_nope.shape
    R, Dv = cfg.kv_lora_rank, cfg.v_head_dim
    w_uk = p[f"{prefix}wkv_b"].astype(dt).reshape(R, H, Dn + Dv)[..., :Dn]
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(dt)
    pad = jnp.zeros((B, H, width - R - q_pe.shape[-1]), dt)
    return jnp.concatenate([q_lat, q_pe.astype(dt), pad], axis=-1)


def _mla_values(cfg: ModelConfig, p: Dict[str, jax.Array], prefix: str,
                o_lat: jax.Array) -> jax.Array:
    """The weighted sum of latent rows' first R columns (B, H, R), taken
    to each head's value width by ``W_UV`` (scope ``mla_absorb``).
    Returns (B, 1, H * Dv)."""
    dt = cdtype(cfg)
    B, H, R = o_lat.shape
    Dn, Dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    w_uv = p[f"{prefix}wkv_b"].astype(dt).reshape(R, H, Dn + Dv)[..., Dn:]
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), w_uv)
    return o.reshape(B, 1, H * Dv)


def _mla_absorbed(cfg: ModelConfig, p: Dict[str, jax.Array], prefix: str,
                  q_nope: jax.Array, q_pe: jax.Array, lat: jax.Array,
                  valid: jax.Array) -> jax.Array:
    """One query per row against latent rows, in absorbed form.

    q_nope (B, H, Dn), q_pe (B, H, Dr); lat (B, T, C): c_kv ‖ k_pe ‖ zeros,
    C >= R + Dr; valid (B, T).  Scores are :func:`_mla_query` dotted with
    each row, the values the rows' first R columns, taken back to each
    head's width after the softmax (:func:`_mla_values`).  Named scopes:
    ``mla_absorb`` (the two absorption matmuls), ``paged_attention``
    (scores, masking, softmax, the weighted sum of latent rows).
    Returns (B, 1, H * Dv)."""
    dt = cdtype(cfg)
    R = cfg.kv_lora_rank
    qf = _mla_query(cfg, p, prefix, q_nope, q_pe, lat.shape[-1])
    with jax.named_scope("paged_attention"):
        s = jnp.einsum("bhc,btc->bht", qf, lat.astype(dt),
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, :], s * mla_softmax_scale(cfg), -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bht,btr->bhr", pr.astype(dt),
                           lat[..., :R].astype(dt))
    return _mla_values(cfg, p, prefix, o_lat)


def mla_decode_attention(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                         p: Dict[str, jax.Array], prefix: str,
                         cache: jax.Array, pos: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """One-token MLA against a dense latent cache (B, T, 1, C), per-slot
    positions.  Returns (out (B,1,D), the cache with the new row)."""
    dt = cdtype(cfg)
    B, T = cache.shape[:2]
    with jax.named_scope("qkv"):
        q_nope, q_pe, lat = _mla_project(cfg, x[:, 0], p, prefix, pos)
    cache = cache.at[jnp.arange(B), jnp.minimum(pos, T - 1), 0].set(
        lat.astype(cache.dtype))
    valid = jnp.arange(T)[None, :] <= pos[:, None]
    o = _mla_absorbed(cfg, p, prefix, q_nope, q_pe, cache[:, :, 0], valid)
    return o @ p[f"{prefix}wo"].astype(dt), cache


def mla_paged_decode_attention(cfg: ModelConfig, plan: ShardingPlan,
                               x: jax.Array, p: Dict[str, jax.Array],
                               prefix: str, pool: jax.Array, layer: jax.Array,
                               page_table: jax.Array, pos: jax.Array
                               ) -> Tuple[jax.Array, jax.Array]:
    """One-token MLA against the *paged* latent pool, read in place.

    pool: (L, P, 1, page, :func:`mla_row_width`), one latent row per token
    and layer; layer, page_table, pos as :func:`paged_decode_attention`.
    Absorbed attention (:func:`_mla_query`, :func:`_mla_values`) is MQA
    with H query heads over the latent rows, each row both key and value
    (its first R columns), through :func:`repro.kernels.ops.paged_attention`
    with the new latent row at ``pos`` given beside the pool.  The pool is
    not written: returns (out (B,1,D), the new row (B, 1, C) in the pool's
    dtype), which the caller writes for every layer at once after its layer
    scan (:func:`write_paged_rows`)."""
    dt = cdtype(cfg)
    with jax.named_scope("qkv"):
        q_nope, q_pe, lat = _mla_project(cfg, x[:, 0], p, prefix, pos)
    lat = lat[:, None, :].astype(pool.dtype)
    qf = _mla_query(cfg, p, prefix, q_nope, q_pe, pool.shape[-1])

    from repro.kernels import ops as kops

    with jax.named_scope("paged_attention"):
        o_lat = kops.paged_attention(qf[:, None], (pool,), (lat,), layer,
                                     page_table, pos,
                                     scale=mla_softmax_scale(cfg),
                                     value_width=cfg.kv_lora_rank)
    o = _mla_values(cfg, p, prefix, o_lat[:, 0])
    return o @ p[f"{prefix}wo"].astype(dt), lat


# --------------------------------------------------------------- embedding
def embed(cfg: ModelConfig, plan: ShardingPlan, table: jax.Array,
          tokens: jax.Array) -> jax.Array:
    """Token gather. The table has ``cfg.padded_vocab`` rows (sharding-
    friendly padding); tokens are always < vocab_size so padding is inert."""
    x = jnp.take(table.astype(cdtype(cfg)), tokens, axis=0)
    return plan.constrain(x, ("batch", "seq", None))


def unembed(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
            table: jax.Array, transpose: bool) -> jax.Array:
    """x @ W_out → logits fp32, vocab-sharded. Padded vocab columns are
    masked to -inf so softmax/argmax semantics match the unpadded vocab."""
    w = table.astype(cdtype(cfg))
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsd,vd->bsv" if transpose else "bsd,dv->bsv",
                            x, w, preferred_element_type=jnp.float32)
        Vp = logits.shape[-1]
        if Vp != cfg.vocab_size:
            pad_mask = jnp.arange(Vp) >= cfg.vocab_size
            logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
        return plan.constrain(logits, ("batch", "seq", "vocab"))


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL; logits fp32 (B,S,V), labels (B,S) int32."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# ------------------------------------------------------------------- remat
def remat_wrap(plan: ShardingPlan, fn):
    if plan.remat_policy == "none":
        return fn
    if plan.remat_policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": recompute everything


def sinusoidal_positions(n: int, d: int) -> np.ndarray:
    """Whisper-style sinusoidal embeddings (n, d) fp32."""
    half = d // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = np.arange(n)[:, None] * freqs[None, :]
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=1).astype(np.float32)
