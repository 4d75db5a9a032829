"""Plain float32 forward pass of a dense decoder, and its lower-precision
control.

The reference follows the configuration file (``model_spec``): RMSNorm or
LayerNorm, rotate-half RoPE, grouped-query causal attention (banded where
the model has a sliding window), SwiGLU or a plain GELU (tanh) MLP, tied
embedding.  It runs one layer at a time over whole sequences in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, with
the benchmark's own weights (``weights.reference_weights``), and imports
nothing of the system under test.

``compare`` takes sequences of prompt + served tokens and returns, for each
served token, how far its reference logit lies below the reference's best,
in units of the reference logits' standard deviation at that position.
With ``control="w8a8"`` it also runs the control: the same forward pass
computed in fp8 (e4m3), the precision below the configuration's bfloat16:
weights with one scale per output channel, and the input of every matrix
multiplication with one scale per token (W8A8), accumulated in float32,
with bfloat16 between operations; for each position it reads the gap of the
token that the control puts first.  ``control="fp8_weights"`` rounds only
the weights to fp8 and computes in bfloat16: a reading, not the control
(PERF.md).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from model_spec import ModelSpec
from weights import reference_weights

PAD = 512     # sequences are padded to a multiple of this (fewer programs)
Q_CHUNK = 512  # queries per attention block
CONTROLS = {"w8a8": True, "fp8_weights": False}  # mode: fp8 matmul inputs


def _norm(m: ModelSpec, x, w):
    xf = x.astype(jnp.float32)
    if m.norm == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + m.eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(
            jnp.mean((xf - mu) ** 2, -1, keepdims=True) + m.eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(m: ModelSpec, x, pos):
    """x: (S, h, Dh); rotate-half with ``rope_theta``."""
    half = x.shape[-1] // 2
    freqs = m.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


def _mm(x, w, dt):
    return jnp.matmul(x.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def _layer(m: ModelSpec, dt, w: Dict[str, jax.Array], x, fp8_inputs=False):
    """One decoder layer over a whole (padded) sequence x: (S, D).
    ``fp8_inputs`` rounds each matrix multiplication's input to fp8 per
    token (the control)."""
    S = x.shape[0]
    H, KV, Dh = m.heads, m.kv_heads, m.head_dim
    G = H // KV
    pos = jnp.arange(S)
    h = _norm(m, x, w["ln1"])
    def mm(a, b):
        if fp8_inputs:
            a = fp8_per_channel(a, axis=-1)
        return _mm(a, b, dt)

    q, k, v = mm(h, w["wq"]), mm(h, w["wk"]), mm(h, w["wv"])
    if m.qkv_bias:
        q = q + w["bq"].astype(dt)
        k = k + w["bk"].astype(dt)
        v = v + w["bv"].astype(dt)
    q = _rope(m, q.reshape(S, H, Dh), pos).reshape(S, KV, G, Dh)
    k = _rope(m, k.reshape(S, KV, Dh), pos)
    v = v.reshape(S, KV, Dh)
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k,
                       preferred_element_type=jnp.float32) / math.sqrt(Dh)
        qpos = (c0 + jnp.arange(qc.shape[0]))[:, None]
        ok = pos[None, :] <= qpos
        if m.window:
            ok = ok & (pos[None, :] > qpos - m.window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p.astype(dt), v,
                               preferred_element_type=jnp.float32).astype(dt))
    o = jnp.concatenate(outs, 0).reshape(S, H * Dh)
    x = x + mm(o, w["wo"])
    h = _norm(m, x, w["ln2"])
    if m.gated:
        a = jax.nn.silu(mm(h, w["w_gate"]).astype(jnp.float32))
        u = (a * mm(h, w["w_in"]).astype(jnp.float32)).astype(dt)
    else:
        u = jax.nn.gelu(mm(h, w["w_in"]).astype(jnp.float32),
                        approximate=True).astype(dt)
    return x + mm(u, w["w_out"])


def fp8_per_channel(w, axis=0):
    """Round to fp8 e4m3 with one scale per slice along ``axis`` (0: per
    output column of an (in, out) weight; -1: per row, i.e. per token of an
    activation or per embedding row), and return it dequantised to
    bfloat16."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _quantize_layer(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {n: fp8_per_channel(a) if a.ndim == 2 else a.astype(jnp.bfloat16)
            for n, a in w.items()}


@partial(jax.jit, static_argnums=0)
def _gaps(m: ModelSpec, xf, table, served):
    """Logits of the final positions; gap of each served token below the
    best, over the logits' std.  xf: (n, D); served: (n,)."""
    logits = jnp.matmul(xf.astype(jnp.float32), table[:m.vocab].T)
    best = logits.max(-1)
    sd = logits.std(-1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return logits, best, sd, (best - got) / sd


@partial(jax.jit, static_argnums=(0, 3))
def _control_pick(m: ModelSpec, xf, qtable, fp8_inputs: bool):
    """The token the control puts first at each position."""
    xq = fp8_per_channel(xf, axis=-1) if fp8_inputs else xf.astype(jnp.bfloat16)
    logits = jnp.matmul(xq, qtable[:m.vocab].T,
                        preferred_element_type=jnp.float32)
    return jnp.argmax(logits, -1)


def compare(m: ModelSpec, seed_key: jax.Array,
            samples: Sequence[Dict[str, Sequence[int]]],
            control: Optional[str] = None) -> List[Dict[str, np.ndarray]]:
    """For each sample ``{"prompt": [...], "served": [...]}`` return
    ``{"gap": (n,)}`` (and ``"control_gap"`` where ``control`` names one of
    ``CONTROLS``)."""
    fp8_inputs = CONTROLS[control] if control else False
    glob, layer_w = reference_weights(m, seed_key)
    seqs, lens = [], []
    for s in samples:
        ctx = list(s["prompt"]) + list(s["served"])[:-1]
        lens.append(len(ctx))
        pad = -(-len(ctx) // PAD) * PAD
        seqs.append(np.asarray(ctx + [0] * (pad - len(ctx)), np.int32))

    with jax.default_matmul_precision("highest"):
        layer_ref = jax.jit(partial(_layer, m, jnp.float32))
        layer_ctl = jax.jit(partial(_layer, m, jnp.bfloat16,
                                    fp8_inputs=fp8_inputs))
        quant = jax.jit(_quantize_layer)
        table = glob["embed"]
        xs = [table[jnp.asarray(t)] for t in seqs]
        if control:
            qtable = jax.jit(partial(fp8_per_channel, axis=-1))(table)
            xc = [qtable[jnp.asarray(t)] for t in seqs]
        for layer in range(m.layers):
            w = layer_w(layer)
            xs = [layer_ref(w, x) for x in xs]
            if control:
                wq = quant(w)
                xc = [layer_ctl(wq, x) for x in xc]
            del w
        out = []
        final = jax.jit(lambda x, w: _norm(m, x, w))
        for i, s in enumerate(samples):
            # the last n positions, padded to a multiple of PAD rows by
            # repeating the last one (fewer programs)
            n = len(s["served"])
            rows = np.arange(lens[i] - n, lens[i])
            rows = np.concatenate([rows, np.full(-n % PAD, rows[-1])])
            served = np.asarray(s["served"], np.int32)
            served = jnp.asarray(np.concatenate([served, np.full(-n % PAD, served[-1])]))
            rows = jnp.asarray(rows)
            logits, best, sd, gap = _gaps(
                m, final(xs[i][rows], glob["final_norm"]), table, served)
            rec = {"gap": np.asarray(gap)[:n]}
            if control:
                pick = _control_pick(m, final(xc[i][rows], glob["final_norm"]),
                                     qtable, fp8_inputs)
                got = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
                rec["control_gap"] = np.asarray((best - got) / sd)[:n]
            out.append(rec)
    return out
