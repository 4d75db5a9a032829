"""Public jit'd wrappers around the Pallas kernels.

Single-source contract (the HPX.Compute claim, DESIGN.md P7): call sites
use these ops everywhere; on TPU they run the Mosaic-compiled kernels, on
CPU they execute the same kernel bodies under ``interpret=True`` — one
source, two backends, identical semantics (tests assert allclose against
``ref.py`` oracles on both paths).  The exception is paged decode
attention (:func:`paged_attention`), which the serving engine runs every
step: off TPU it takes the XLA gather path, which is also the kernel
tests' oracle, rather than the interpreter.

Wrappers own the ugly parts: GQA head broadcasting, layout flattening to
kernel-friendly (rows, seq, feature) shapes, and padding to block multiples.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention_fwd,
                                            paged_attention_fwd)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.stream import triad as _triad_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,S,H,Dh), k/v: (B,S,KV,Dh) → (B,S,H,Dh). GQA via H % KV == 0."""
    if interpret is None:
        interpret = not _on_tpu()
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, S, KV, G, Dh).transpose(0, 2, 3, 1, 4).reshape(B * KV * G, S, Dh)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * KV * G, S, Dh)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * KV * G, S, Dh)
    qr, S0 = _pad_to(qr, 1, max(block_q, block_k))
    kr, _ = _pad_to(kr, 1, max(block_q, block_k))
    vr, _ = _pad_to(vr, 1, max(block_q, block_k))
    o = flash_attention_fwd(qr, kr, vr, causal=causal, window=window,
                            block_q=block_q, block_k=block_k, valid_len=S0,
                            interpret=interpret)
    o = o[:, :S0]
    return o.reshape(B, KV, G, S0, Dh).transpose(0, 3, 1, 2, 4).reshape(B, S0, H, Dh)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, *, block_k: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,H,Dh), k/v: (B,T,KV,Dh) → (B,H,Dh).

    ``length`` is the valid cache prefix: a scalar (uniform fill) or (B,)
    per-slot (continuous batching — every slot at its own depth)."""
    if interpret is None:
        interpret = not _on_tpu()
    B, H, Dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    block_k = min(block_k, -(-T // 128) * 128)
    kr, _ = _pad_to(k.transpose(0, 2, 1, 3), 2, block_k)  # (B, KV, T, Dh)
    vr, _ = _pad_to(v.transpose(0, 2, 1, 3), 2, block_k)
    lengths = jnp.broadcast_to(
        jnp.minimum(jnp.asarray(length, jnp.int32), T), (B,))
    o = decode_attention_fwd(q.reshape(B, KV, H // KV, Dh), kr, vr, lengths,
                             block_k=block_k, interpret=interpret)
    return o.reshape(B, H, Dh)


# The paged kernel's block: at least this many bytes of K/V (or latent)
# copied per block, whatever the page geometry.
BLOCK_BYTES = 512 << 10
RING = 4  # blocks in the kernel's ring: one computing, the rest in flight


def pages_per_block(pools, page: int, maxp: int) -> int:
    """Pages per kernel block: the fewest, in a power of two, for one
    block's copy (every pool's pages) to hold :data:`BLOCK_BYTES`, and no
    more than a row's ``maxp``."""
    row = sum(p.shape[2] * p.shape[4] * p.dtype.itemsize for p in pools)
    n = -(-BLOCK_BYTES // (page * row))
    return min(1 << (n - 1).bit_length(), maxp)


def paged_attention(q: jax.Array, pools, new, layer: jax.Array,
                    page_table: jax.Array, pos: jax.Array, *, scale: float,
                    value_width: int) -> jax.Array:
    """One-token attention over stacked paged pools, read in place (P7: the
    Pallas kernel on TPU, the XLA gather elsewhere).

    q: (B, KV, G, W); pools: (K, V) or one latent pool whose rows are key
    and value (the value is a row's first ``value_width`` columns), each
    (L, P, KV, page, W); new: the step's new row per pool, (B, KV, W), at
    position ``pos`` (B,) — not in the pools; layer: () int32; page_table:
    (B, maxp) int32.  Row b attends positions ``[0, pos[b]]``.
    Returns (B, KV, G, value_width).
    """
    fn = paged_attention_kernel if _on_tpu() else paged_attention_gather
    return fn(q, pools, new, layer, page_table, pos, scale=scale,
              value_width=value_width)


def paged_attention_kernel(q, pools, new, layer, page_table, pos, *,
                           scale: float, value_width: int,
                           interpret: Optional[bool] = None) -> jax.Array:
    """:func:`paged_attention` through the Pallas kernel
    (:func:`~repro.kernels.decode_attention.paged_attention_fwd`), in
    interpret mode off TPU.  Each row reads only its live pages."""
    if interpret is None:
        interpret = not _on_tpu()
    return paged_attention_fwd(
        q, tuple(pools), tuple(new), layer, page_table, pos, scale=scale,
        value_width=value_width,
        pages_per_block=pages_per_block(pools, pools[0].shape[3],
                                        page_table.shape[1]),
        buffers=RING, interpret=interpret)


def paged_attention_gather(q, pools, new, layer, page_table, pos, *,
                           scale: float, value_width: int) -> jax.Array:
    """:func:`paged_attention` in XLA: each pool's page list of every row
    gathered in full (:func:`gather_layer_pages`), the new rows set in at
    ``pos``, then a masked softmax over all ``maxp · page`` positions."""
    B = q.shape[0]
    rows = jnp.arange(B)
    kv = [gather_layer_pages(p, layer, page_table).at[rows, :, pos].set(n)
          for p, n in zip(pools, new)]
    k, v = kv[0], kv[-1][..., :value_width]
    s = jnp.einsum("bkgd,bktd->bkgt", q, k,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(k.shape[2])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,bktd->bkgd", pr.astype(v.dtype), v)


def gather_layer_pages(pool: jax.Array, layer: jax.Array,
                       page_table: jax.Array) -> jax.Array:
    """One layer's pages of every request, read in place from a stacked pool.

    pool: (L, P, KV, page, Dh), layer: () int32, page_table: (B, maxp)
    → (B, KV, maxp·page, Dh), head-major as the pages are.  One gather
    indexed by (layer, page): ``pool[layer]`` is never materialized.
    Out-of-range pages read NaN, as ``jnp.take``'s default fill mode does.
    """
    L, P, KV, page, Dh = pool.shape
    B, maxp = page_table.shape
    idx = jnp.stack([jnp.broadcast_to(layer, page_table.shape).astype(
        page_table.dtype), page_table], axis=-1)
    g = jax.lax.gather(
        pool, idx,
        jax.lax.GatherDimensionNumbers(offset_dims=(2, 3, 4),
                                       collapsed_slice_dims=(0, 1),
                                       start_index_map=(0, 1)),
        slice_sizes=(1, 1, KV, page, Dh),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return g.transpose(0, 2, 1, 3, 4).reshape(B, KV, maxp * page, Dh)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256,
             interpret: Optional[bool] = None) -> jax.Array:
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,G,N) → y (B,S,H,P)."""
    if interpret is None:
        interpret = not _on_tpu()
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    xk = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtk = dt.transpose(0, 2, 1).reshape(B * H, S)
    Ak = jnp.tile(A, B)
    Bk = jnp.repeat(Bm.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, S, N)
    Ck = jnp.repeat(Cm.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, S, N)
    xk, S0 = _pad_to(xk, 1, chunk)
    dtk, _ = _pad_to(dtk, 1, chunk)
    Bk, _ = _pad_to(Bk, 1, chunk)
    Ck, _ = _pad_to(Ck, 1, chunk)
    y = ssd_scan_fwd(xk, dtk, Ak, Bk, Ck, chunk=chunk, interpret=interpret)
    return y[:, :S0].reshape(B, H, S0, P).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, block_s: int = 256,
               block_w: int = 128, interpret: Optional[bool] = None) -> jax.Array:
    """h_t = a_t·h_{t-1} + b_t. a/b: (B,S,W) → (B,S,W)."""
    if interpret is None:
        interpret = not _on_tpu()
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    a2, S0 = _pad_to(a, 1, block_s)
    b2, _ = _pad_to(b, 1, block_s)
    a2, W0 = _pad_to(a2, 2, block_w)
    b2, _ = _pad_to(b2, 2, block_w)
    h = rglru_scan_fwd(a2, b2, block_s=block_s, block_w=block_w,
                       interpret=interpret)
    return h[:, :S0, :W0]


@functools.partial(jax.jit, static_argnames=("alpha", "block", "interpret"))
def stream_triad(a: jax.Array, b: jax.Array, alpha: float = 3.0, *,
                 block: int = 65536, interpret: Optional[bool] = None) -> jax.Array:
    if interpret is None:
        interpret = not _on_tpu()
    (N,) = a.shape
    block = min(block, N)
    a2, N0 = _pad_to(a, 0, block)
    b2, _ = _pad_to(b, 0, block)
    return _triad_kernel(a2, b2, alpha, block=block, interpret=interpret)[:N0]


# ------------------------------------------------------------ trainable flash
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_trainable(q: jax.Array, k: jax.Array, v: jax.Array,
                              causal: bool = True, window: int = 0) -> jax.Array:
    """Training-path flash attention: Pallas forward kernel + exact backward.

    Backward recomputes attention in the pure-jnp oracle and differentiates
    it (flash-style recompute — no score materialization is *saved*, the
    memory win is in the forward; a fused backward kernel is the natural
    next TPU optimization and is noted in EXPERIMENTS.md)."""
    return flash_attention(q, k, v, causal=causal, window=window)


def _fat_fwd(q, k, v, causal, window):
    return flash_attention(q, k, v, causal=causal, window=window), (q, k, v)


def _fat_bwd(causal, window, res, ct):
    from repro.kernels import ref

    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.mha(q_, k_, v_, causal=causal,
                                                window=window), q, k, v)
    return vjp(ct)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)
