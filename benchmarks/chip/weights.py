"""Random weights from the workload seed, made by the benchmark.

Each leaf of each layer has its own key, ``fold_in(fold_in(root, crc32(name)),
layer)``, so the served weights (all layers stacked, made on the device in
one jitted call, in bf16) and the reference's weights (one layer at a time,
in float32) hold the same values without either reading the other.

Leaves, by their plain names:

    embed (padded_vocab, hidden)   final_norm (hidden,)
    per layer: ln1, wq, bq, wk, bk, wv, bv, wo, ln2, w_in, [w_gate], w_out

Matrices are N(0, 1/fan_in), the embedding N(0, 0.02²), biases N(0, 0.05²),
norm scales 1 + N(0, 0.05²): biases and scales that differ from 0 and 1 make
a dropped bias or norm weight show in the logits.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from model_spec import ModelSpec

# The system's parameter names for each plain leaf (a dense decoder stacked
# over layers under "blk/").  A leaf the system has and this map lacks, such
# as an untied "lm_head", is an error in ``served_params``.
_SERVED = {"embed": "tok_embed", "final_norm": "final_ln"}
_LAYER_PREFIX = "blk/"


def root_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (two 32-bit words)."""
    words = np.random.SeedSequence(int(seed) & (2**64 - 1)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def layer_shapes(m: ModelSpec) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    D, F = m.hidden, m.ffn
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    shapes = {"ln1": ((D,), "norm"), "wq": ((D, q), "matrix"),
              "wk": ((D, kv), "matrix"), "wv": ((D, kv), "matrix"),
              "wo": ((q, D), "matrix"), "ln2": ((D,), "norm"),
              "w_in": ((D, F), "matrix"), "w_out": ((F, D), "matrix")}
    if m.qkv_bias:
        shapes.update(bq=((q,), "bias"), bk=((kv,), "bias"),
                      bv=((kv,), "bias"))
    if m.gated:
        shapes["w_gate"] = ((D, F), "matrix")
    return shapes


def global_shapes(m: ModelSpec) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    return {"embed": ((m.padded_vocab, m.hidden), "embed"),
            "final_norm": ((m.hidden,), "norm")}


def _leaf(key: jax.Array, name: str, layer, shape, kind: str) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        x = z * (1.0 / np.sqrt(shape[0]))
    elif kind == "embed":
        x = z * 0.02
    elif kind == "bias":
        x = z * 0.05
    else:  # norm scale
        x = 1.0 + z * 0.05
    return x.astype(jnp.bfloat16)


def served_params(m: ModelSpec, key: jax.Array,
                  expected: Dict[str, Tuple[Tuple[int, ...], str]]
                  ) -> Dict[str, jax.Array]:
    """All weights in the system's layout, bf16, in one jitted call.

    ``expected`` maps each of the system's parameter names to (shape, dtype
    name); any difference in names, shapes or dtype is an error."""
    names = {n: _SERVED[n] for n in global_shapes(m)}
    names.update({n: _LAYER_PREFIX + n for n in layer_shapes(m)})
    want = {names[n]: (tuple(s), "bfloat16")
            for n, (s, _) in global_shapes(m).items()}
    want.update({names[n]: ((m.layers,) + tuple(s), "bfloat16")
                 for n, (s, _) in layer_shapes(m).items()})
    if want != expected:
        missing = sorted(set(expected) - set(want))
        extra = sorted(set(want) - set(expected))
        differ = sorted(k for k in set(want) & set(expected)
                        if want[k] != expected[k])
        raise ValueError(f"the system's parameters differ from {m.name}'s: "
                         f"not made here {missing}, not in the system "
                         f"{extra}, other shape or dtype {differ}")

    def make(key):
        out = {names[n]: _leaf(key, n, 0, s, kind)
               for n, (s, kind) in global_shapes(m).items()}
        layers = jnp.arange(m.layers)
        for n, (s, kind) in layer_shapes(m).items():
            out[names[n]] = jax.vmap(
                lambda l, n=n, s=s, kind=kind: _leaf(key, n, l, s, kind))(layers)
        return out

    return jax.jit(make)(key)


def reference_weights(m: ModelSpec, key: jax.Array
                      ) -> Tuple[Dict[str, jax.Array], Callable[[int], Dict[str, jax.Array]]]:
    """(global leaves, layer -> leaves), float32 holding the bf16 values."""
    glob = jax.jit(lambda k: {n: _leaf(k, n, 0, s, kind).astype(jnp.float32)
                              for n, (s, kind) in global_shapes(m).items()})(key)
    shapes = layer_shapes(m)
    one = jax.jit(lambda k, l: {n: _leaf(k, n, l, s, kind).astype(jnp.float32)
                                for n, (s, kind) in shapes.items()})
    return glob, lambda layer: one(key, jnp.int32(layer))
