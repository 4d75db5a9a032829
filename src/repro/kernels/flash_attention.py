"""Flash attention forward kernel (TPU Pallas): blocked online-softmax.

TPU adaptation of FlashAttention [arXiv:2205.14135] — the HBM→VMEM
hierarchy replaces SRAM tiling: a Q block of ``block_q`` rows lives in
VMEM, the kernel streams K/V blocks of ``block_k`` rows through it and keeps
the running (max, sum, acc) online-softmax state in fp32 VMEM scratch.  No
score leaves VMEM.  Both dots take their operands in the input dtype (bf16
on the MXU) and accumulate in fp32; P is cast to the value dtype before PV,
as the XLA path (``layers._sdpa``) does.

Layout is head-major: q (B, H, S, Dk), k (B, KV, S, Dk), v (B, KV, S, Dv),
out (B, H, S, Dv).  Key and value widths come from the inputs (GQA: Dk = Dv
= Dh; DeepSeek-V2's latent attention in its plain form: Dk = 192, Dv =
128, KV = H).  Grid: (batch, query head, Q blocks, K blocks), the K axis
sequential (the carry lives in scratch across it).  GQA without copies:
the K/V ``index_map`` maps query head ``h`` to its KV head ``h // G``.

Each batch row's valid length is scalar-prefetched into SMEM, so one
compiled kernel serves every prompt length of a bucket.  ``pl.when`` skips
each K block above the causal diagonal, outside the window or past the
valid length, and each Q block past it; the K/V ``index_map`` clamps those
steps to a block the pipeline already holds, so they issue no DMA.  Blocks
wholly inside the causal triangle and the valid length take an unmasked
body.  Rows past the valid length are written as zeros; keys past it are
masked by score and their values zeroed, so whatever they hold (NaN
included) reaches no sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr,
                      *, scale: float, block_q: int, block_k: int,
                      causal: bool, window: int):
    """Prefetched valid lengths (B,), then q (1, 1, block_q, Dk), k
    (1, 1, block_k, Dk), v (1, 1, block_k, Dv) and o (1, 1, block_q, Dv)
    blocks; scratch m, l (block_q, 1) and acc (block_q, Dv) fp32."""
    valid = len_ref[pl.program_id(0)]
    q_start = pl.program_id(2) * block_q
    ki = pl.program_id(3)
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = kpos < valid
            if causal:
                ok = jnp.logical_and(ok, kpos <= qpos)
            if window > 0:
                ok = jnp.logical_and(ok, kpos > qpos - window)
            s = jnp.where(ok, s, NEG_INF)
            vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(vpos < valid, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    q_last = q_start + block_q - 1
    k_last = k_start + block_k - 1
    run = jnp.logical_and(q_start < valid, k_start < valid)
    full = k_last < valid
    if causal:
        run = jnp.logical_and(run, k_start <= q_last)
        full = jnp.logical_and(full, k_last <= q_start)
    if window > 0:
        run = jnp.logical_and(run, k_last > q_start - window)
        full = jnp.logical_and(full, k_start > q_last - window)
    pl.when(jnp.logical_and(run, full))(functools.partial(_step, False))
    pl.when(jnp.logical_and(run, jnp.logical_not(full)))(
        functools.partial(_step, True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
        o_ref[0, 0] = jnp.where(qpos < valid, o, 0.0).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        valid_len: jax.Array, *, scale: float,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, S, Dk); k: (B, KV, S, Dk); v: (B, KV, S, Dv) with H a
    multiple of KV; valid_len: (B,) int32, each row's count of valid
    positions (at least 1).  Returns (B, H, S, Dv) in q's dtype, zero past
    each row's valid length.  S must be a multiple of both blocks (ops.py
    pads)."""
    B, H, S, Dk = q.shape
    KV, Dv = k.shape[1], v.shape[3]
    G = H // KV
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)

    def kv_index(b, h, qi, ki, lens):
        last = jnp.maximum(lens[b] - 1, 0)
        tail = last // block_k  # the last block any query needs
        if causal:
            last = jnp.minimum(last, qi * block_q + block_q - 1)
        kb = jnp.minimum(ki, last // block_k)
        if window > 0:
            kb = jnp.maximum(kb, jnp.maximum(qi * block_q - window + 1, 0)
                             // block_k)
            kb = jnp.minimum(kb, tail)
        # a Q block past the valid length keeps the block the last valid
        # one ended on
        return (b, h // G, jnp.where(qi * block_q <= last, kb, tail), 0)

    def q_index(b, h, qi, ki, lens):
        return (b, h, jnp.minimum(qi, jnp.maximum(lens[b] - 1, 0) // block_q), 0)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dk), q_index),
            pl.BlockSpec((1, 1, block_k, Dk), kv_index),
            pl.BlockSpec((1, 1, block_k, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, qi, ki, lens: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(valid_len, jnp.int32), q, k, v)
