"""Plain float32 forward pass of the configuration's model, and its
lower-precision control.

The reference follows the configuration file: its family module
(``families/<model_type>.py``) gives one layer's forward pass over a whole
sequence, the final norm and the logits table (tied or not), and this
module runs the layers in order, one at a time, over whole sequences in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``,
with the benchmark's own weights (``weights.reference_weights``).  It
imports nothing of the system under test.

``compare`` takes sequences of prompt + served tokens and returns, for each
served token, how far its reference logit lies below the reference's best,
in units of the reference logits' standard deviation at that position.
With ``control="w8a8"`` it also runs the control: the same forward pass
computed in fp8 (e4m3), the precision below the configuration's bfloat16:
every leaf of kind ``matrix``, whatever its rank, with one scale per output
channel, the embedding with one scale per row, and the input of every
matrix multiplication with one scale per token (W8A8), accumulated in
float32, with bfloat16 between operations; for each position it reads the
gap of the token that the control puts first.  ``control="fp8_weights"``
rounds only the weights to fp8 and computes in bfloat16: a reading, not the
control (PERF.md).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import manifest
from model_spec import ModelSpec
from weights import reference_weights

PAD = 512     # sequences are padded to a multiple of this (fewer programs)
CONTROLS = {"w8a8": True, "fp8_weights": False}  # mode: fp8 matmul inputs


def _matmul(a, b, dt, fp8_inputs=False):
    """``a @ b`` in ``dt``, accumulated in float32; ``fp8_inputs`` rounds
    ``a`` to fp8 per token first (the control)."""
    if fp8_inputs:
        a = fp8_per_channel(a, axis=-1)
    return jnp.matmul(a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def fp8_per_channel(w, axis=-2):
    """Round to fp8 e4m3 with one scale per slice along ``axis`` (-2: per
    output column of an (..., in, out) weight; -1: per row, i.e. per token
    of an activation or per embedding row), and return it dequantised to
    bfloat16."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _quantize(w: Dict[str, jax.Array], kinds: Dict[str, str]
              ) -> Dict[str, jax.Array]:
    """The control's weights: matrices in fp8 per output channel (the last
    axis; the second-to-last is reduced), the embedding per row, the rest
    in bfloat16."""
    def q(n, a):
        if kinds[n] == "matrix":
            return fp8_per_channel(a)
        if kinds[n] == "embed":
            return fp8_per_channel(a, axis=-1)
        return a.astype(jnp.bfloat16)
    return {n: q(n, a) for n, a in w.items()}


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
    fam = manifest.family(m.model_type)
    layout = fam.layout(m)
    fp8_inputs = CONTROLS[control] if control else False
    glob, layer_w = reference_weights(layout, seed_key)
    seqs, lens = [], []
    for s in samples:
        ctx = list(s["prompt"]) + list(s["served"])[:-1]
        lens.append(len(ctx))
        pad = -(-len(ctx) // PAD) * PAD
        seqs.append(np.asarray(ctx + [0] * (pad - len(ctx)), np.int32))

    with jax.default_matmul_precision("highest"):
        quant = jax.jit(partial(_quantize, kinds=layout.kinds()))
        xs = [glob["embed"][jnp.asarray(t)] for t in seqs]
        if control:
            qglob = quant(glob)
            xc = [qglob["embed"][jnp.asarray(t)] for t in seqs]
        for st in sorted(layout.stacks, key=lambda st: st.first):
            layer_ref = jax.jit(partial(
                fam.layer, m, st.prefix, jnp.float32,
                mm=partial(_matmul, dt=jnp.float32)))
            layer_ctl = jax.jit(partial(
                fam.layer, m, st.prefix, jnp.bfloat16,
                mm=partial(_matmul, dt=jnp.bfloat16, fp8_inputs=fp8_inputs)))
            for layer in range(st.first, st.first + st.layers):
                w = layer_w(layer)
                xs = [layer_ref(w, x) for x in xs]
                if control:
                    wq = quant(w)
                    xc = [layer_ctl(wq, x) for x in xc]
                del w
        out = []
        final = jax.jit(lambda x, g: fam.final(m, g, x))
        table = fam.logits_table(m, glob)
        if control:
            qtable = fam.logits_table(m, qglob)
        for i, s in enumerate(samples):
            # the last n positions, padded to a multiple of PAD rows by
            # repeating the last one (fewer programs)
            n = len(s["served"])
            rows = np.arange(lens[i] - n, lens[i])
            rows = np.concatenate([rows, np.full(-n % PAD, rows[-1])])
            served = np.asarray(s["served"], np.int32)
            served = jnp.asarray(np.concatenate([served, np.full(-n % PAD, served[-1])]))
            rows = jnp.asarray(rows)
            logits, best, sd, gap = _gaps(m, final(xs[i][rows], glob), table,
                                          served)
            rec = {"gap": np.asarray(gap)[:n]}
            if control:
                pick = _control_pick(m, final(xc[i][rows], qglob), qtable,
                                     fp8_inputs)
                got = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
                rec["control_gap"] = np.asarray((best - got) / sd)[:n]
            out.append(rec)
    return out
