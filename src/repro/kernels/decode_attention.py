"""Flash-decoding kernels (TPU Pallas): one-token attention over a long KV
cache, KV-blocked with a running log-sum-exp combine.

Decode attention is memory-bound (the whole cache streams HBM→VMEM once per
token); the kernel's job is to keep that stream dense and the softmax state
in registers/VMEM.  Grid: (batch, KV blocks) with the KV dim sequential —
(m, l, acc) scratch carries the online softmax across KV blocks, exactly the
combine that GSPMD emits across *devices* when the cache is
sequence-sharded (DESIGN.md §5) — same math, one level down.

Blocking follows the TPU tiling rule (the last two block dims divisible by
(8, 128) or equal to the array's): one grid row holds *all* query heads of
one request as ``(KV, G, Dh)`` — the GQA group ``G`` is a full array dim, so
any group size lowers (qwen2.5-3b G=8, starcoder2-3b G=12) — and K/V are
laid out head-major, ``(..., KV, tokens, Dh)``, so a block's last two dims
are ``(tokens, Dh)``.  Each KV head's block is read once and shared by its
``G`` query heads: no ``jnp.repeat`` of the cache.

Two variants share the kernel body and differ only in the K/V index map:

- :func:`decode_attention_fwd` — dense cache ``(B, KV, T, Dh)``;
- :func:`paged_decode_attention_fwd` — block pool ``(P, KV, page, Dh)``
  shared by all requests; each request walks *its own* page list via an
  SMEM-prefetched page table (the index map reads the table before the DMA
  is issued, so the gather costs nothing extra — "sending work to data" at
  the memory-system level).

``lengths`` is per request: slots at different fill depths mask their own
prefix.  Blocks past a request's fill re-select its last valid block, so
the pipeline issues no DMA for them, and their compute is skipped.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, *refs, scale: float, block_k: int):
    """Prefetched scalars (lengths, then the page table when paged), then
    q/o blocks (1, KV, G, Dh), k/v blocks (1, KV, block_k, Dh) and scratch
    m/l (KV, G, 1), acc (KV, G, Dh) fp32."""
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs[-7:]
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(k_start < length)
    def _body():
        for h in range(q_ref.shape[1]):  # KV heads (static, small)
            q = q_ref[0, h].astype(jnp.float32)  # (G, Dh)
            k = k_ref[0, h].astype(jnp.float32)  # (bk, Dh)
            v = v_ref[0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)  # (G, bk)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def _call(index_k, grid, q, k, v, scalars, *, block_k: int, interpret: bool):
    """``scalars`` = (lengths,) or (lengths, page_table), scalar-prefetched
    into SMEM; ``index_k`` maps (b, ki, *scalars) to the K/V block."""
    B, KV, G, Dh = q.shape
    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(Dh),
                               block_k=block_k)
    kv_spec = pl.BlockSpec((1, KV, block_k, Dh), index_k)
    q_spec = pl.BlockSpec((1, KV, G, Dh), lambda b, ki, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q, k, v)


def decode_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Dense flash-decode.

    q: (B, KV, G, Dh); k/v: (B, KV, T, Dh) with T a multiple of ``block_k``
    (ops.py pads); lengths: (B,) int32 valid prefix per request.
    Returns (B, KV, G, Dh)."""
    B, KV, T, Dh = k.shape
    assert T % block_k == 0, (T, block_k)

    def kv_index(b, ki, ln):
        last = jnp.maximum(ln[b] - 1, 0) // block_k
        return (b, 0, jnp.minimum(ki, last), 0)

    return _call(kv_index, (B, T // block_k), q, k, v,
                 (jnp.asarray(lengths, jnp.int32),),
                 block_k=block_k, interpret=interpret)


def paged_decode_attention_fwd(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, page_table: jax.Array,
                               lengths: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """Paged flash-decode.

    q: (B, KV, G, Dh); k_pages/v_pages: (P, KV, page, Dh) block pool shared
    by all requests; page_table: (B, maxp) int32 — page_table[b, j] is the
    pool page holding tokens [j·page, (j+1)·page) of request b (entries past
    the fill must be *valid* indices, e.g. 0 — they are never read);
    lengths: (B,) int32 valid prefix per request.

    Grid is (B, maxp); the page walk is sequential per request and the page
    table + lengths are scalar-prefetched so each block's DMA source
    address is known up front.  Returns (B, KV, G, Dh).
    """
    P, KV, page, Dh = k_pages.shape
    B, maxp = page_table.shape
    assert q.shape[:2] == (B, KV), (q.shape, B, KV)

    def kv_index(b, ki, ln, pt):
        last = jnp.maximum(ln[b] - 1, 0) // page
        return (pt[b, jnp.minimum(ki, last)], 0, 0, 0)

    return _call(kv_index, (B, maxp), q, k_pages, v_pages,
                 (jnp.asarray(lengths, jnp.int32),
                  jnp.asarray(page_table, jnp.int32)),
                 block_k=page, interpret=interpret)
