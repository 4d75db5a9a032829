"""Decode-attention kernels (TPU Pallas): one query token per request
against a long K/V history, with a running log-sum-exp (online softmax) in
fp32.

Decode attention is memory-bound: every key and value a row attends streams
HBM→VMEM once per token.  The kernels' job is to keep that stream dense,
read nothing a row does not attend, and hold the softmax state in VMEM.

- :func:`decode_attention_fwd` — dense per-request caches
  ``(B, KV, T, Dh)``.  Grid (batch, KV blocks), the KV axis sequential;
  blocks past a request's fill re-select its last valid block, so the
  pipeline issues no DMA for them, and their compute is skipped.
- :func:`paged_attention_fwd` — the serving engine's paged cache: stacked
  block pools ``(L, P, KV, page, W)`` shared by every request and layer,
  read in place.  Each row walks only its own live pages, page by page
  DMA'd from HBM into a ring of VMEM blocks, the next blocks' copies in
  flight while the current block computes.

Blocking follows the TPU tiling rule (the last two block dims divisible by
(8, 128) or equal to the array's): one request's query heads are
``(KV, G, W)`` — the GQA group ``G`` is a full array dim, so any group size
lowers (qwen2.5-3b G=8, starcoder2-3b G=12) — and K/V are head-major,
``(..., KV, tokens, W)``, so a block's last two dims are ``(tokens, W)``.
Each KV head's block is read once and shared by its ``G`` query heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_k: int):
    """Prefetched lengths, then q/o blocks (1, KV, G, Dh), k/v blocks
    (1, KV, block_k, Dh) and scratch m/l (KV, G, 1), acc (KV, G, Dh) fp32."""
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


def decode_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Dense flash-decode.

    q: (B, KV, G, Dh); k/v: (B, KV, T, Dh) with T a multiple of ``block_k``
    (ops.py pads); lengths: (B,) int32 valid prefix per request.
    Returns (B, KV, G, Dh)."""
    B, KV, T, Dh = k.shape
    G = q.shape[2]
    assert T % block_k == 0, (T, block_k)

    def kv_index(b, ki, ln):
        last = jnp.maximum(ln[b] - 1, 0) // block_k
        return (b, 0, jnp.minimum(ki, last), 0)

    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(Dh),
                               block_k=block_k)
    kv_spec = pl.BlockSpec((1, KV, block_k, Dh), kv_index)
    q_spec = pl.BlockSpec((1, KV, G, Dh), lambda b, ki, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, T // block_k),
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
    )(jnp.asarray(lengths, jnp.int32), q, k, v)


# ------------------------------------------------------------ paged decode
def _paged_kernel(layer_ref, len_ref, pt_ref, q_ref, *refs, npool: int,
                  scale: float, value_width: int, pages_per_block: int,
                  buffers: int):
    """One layer of paged decode attention for the whole batch.

    Scalar-prefetched: the layer index (1,), each row's count of tokens in
    the pool (B,) and the flattened page table (B·maxp,).  Then q
    (B, KV, G, W) and the step's new rows (one (B, KV, W) per pool) in
    VMEM; the stacked pools (L, P, KV, page, W) left in HBM; the output
    (B, KV, G, value_width).  Scratch: a ring of ``buffers`` blocks of
    ``pages_per_block`` pages per pool, their DMA semaphores, and the
    online softmax's m, l (B, KV, G, 1) and acc (B, KV, G, value_width).

    The work is one flat sequence of (row, block) items over the rows that
    hold pages, so the ring stays full across rows: the copies of item
    ``n + buffers - 1`` are started before item ``n`` is waited for.  A
    block copies only its live pages, one DMA each; the last block of a row
    has its tail masked (keys by score, values to 0), so neither stale nor
    never-written buffer rows reach the sums.
    """
    new_refs = refs[:npool]
    pools = refs[npool:2 * npool]
    o_ref = refs[2 * npool]
    bufs = refs[2 * npool + 1:3 * npool + 1]
    sems, m_scr, l_scr, acc_scr = refs[3 * npool + 1:]
    B, KV, G, W = q_ref.shape
    page = pools[0].shape[3]
    maxp = pt_ref.shape[0] // B
    bk = pages_per_block * page
    layer = layer_ref[0]

    def blocks(b):
        return (len_ref[b] + bk - 1) // bk

    def first_row(b):  # first row at or after b with a page in the pool
        return jax.lax.while_loop(
            lambda r: (r < B) & (len_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1, b)

    def advance(b, i):  # the item after (b, i)
        more = i + 1 < blocks(b)
        nb = jax.lax.cond(more, lambda: b, lambda: first_row(b + 1))
        return nb, jnp.where(more, i + 1, 0)

    def copies(b, i, slot, wait):
        live = len_ref[b]
        for j in range(pages_per_block):
            p = i * pages_per_block + j

            @pl.when(p * page < live)
            def _():
                pid = pt_ref[b * maxp + p]
                for n in range(npool):
                    cp = pltpu.make_async_copy(
                        pools[n].at[layer, pid],
                        bufs[n].at[slot, :, pl.ds(j * page, page)],
                        sems.at[n, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    # the new token at position len: its score opens each row's softmax
    q = q_ref[...].astype(jnp.float32)
    kn = new_refs[0][...].astype(jnp.float32)[:, :, None, :]
    m_scr[...] = jnp.sum(q * kn, axis=-1, keepdims=True) * scale
    l_scr[...] = jnp.ones_like(l_scr)
    vn = new_refs[-1][:, :, :value_width].astype(jnp.float32)
    acc_scr[...] = jnp.broadcast_to(vn[:, :, None, :], acc_scr.shape)

    def compute(b, i, slot):
        length = len_ref[b]
        start = i * bk

        @pl.when(start + bk > length)  # a row's last block: zero its tail
        def _():
            v = bufs[-1][slot]
            vpos = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            bufs[-1][slot] = jnp.where(vpos < length, v, jnp.zeros_like(v))

        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        for h in range(KV):
            k = bufs[0][slot, h]
            v = bufs[-1][slot, h, :, :value_width]
            s = jax.lax.dot_general(q_ref[b, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(kpos < length, s * scale, NEG_INF)
            m_prev = m_scr[b, h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[b, h] = l_scr[b, h] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_scr[b, h] = acc_scr[b, h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[b, h] = m_new

    b0 = first_row(0)
    fetch = (b0, jnp.int32(0))
    for n in range(buffers - 1):  # fill the ring
        @pl.when(fetch[0] < B)
        def _():
            copies(*fetch, n, wait=False)
        fetch = jax.lax.cond(fetch[0] < B, lambda f=fetch: advance(*f),
                             lambda f=fetch: f)

    def body(c):
        b, i, fb, fi, n = c

        @pl.when(fb < B)
        def _():
            copies(fb, fi, (n + buffers - 1) % buffers, wait=False)
        fb, fi = jax.lax.cond(fb < B, lambda: advance(fb, fi),
                              lambda: (fb, fi))
        slot = n % buffers
        copies(b, i, slot, wait=True)
        compute(b, i, slot)
        return (*advance(b, i), fb, fi, n + 1)

    jax.lax.while_loop(lambda c: c[0] < B, body,
                       (b0, jnp.int32(0), *fetch, jnp.int32(0)))
    o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def paged_attention_fwd(q: jax.Array, pools, new, layer: jax.Array,
                        page_table: jax.Array, lengths: jax.Array, *,
                        scale: float, value_width: int, pages_per_block: int,
                        buffers: int, interpret: bool = False) -> jax.Array:
    """Paged decode attention over stacked pools, read in place.

    q: (B, KV, G, W); pools: a tuple of stacked pools (L, P, KV, page, W),
    (K, V) or one pool whose rows are both key and value (latent attention:
    the value is a row's first ``value_width`` columns); new: the step's
    new row per pool, (B, KV, W) each, not in the pools; layer: () int32;
    page_table: (B, maxp) int32; lengths: (B,) int32, the tokens of each
    row in the pool — row b attends positions ``[0, lengths[b])`` read from
    its pages ``page_table[b, :ceil(lengths[b] / page)]`` and the new row
    at position ``lengths[b]``; pages past those are never read.  A block
    is ``pages_per_block`` pages; the ring holds ``buffers`` blocks, one
    computing while the others' copies are in flight.
    Returns (B, KV, G, value_width) in q's dtype.
    """
    B, KV, G, W = q.shape
    npool = len(pools)
    assert len(new) == npool and npool in (1, 2), (len(new), npool)
    bk = pages_per_block * pools[0].shape[3]
    kernel = functools.partial(
        _paged_kernel, npool=npool, scale=scale, value_width=value_width,
        pages_per_block=pages_per_block, buffers=buffers)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[vmem] * (1 + npool) + [hbm] * npool,
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((buffers, KV, bk, W), p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((npool, buffers)),
            pltpu.VMEM((B, KV, G, 1), jnp.float32),
            pltpu.VMEM((B, KV, G, 1), jnp.float32),
            pltpu.VMEM((B, KV, G, value_width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, value_width), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.asarray(lengths, jnp.int32),
      jnp.asarray(page_table, jnp.int32).reshape(-1),
      q, *new, *pools)
