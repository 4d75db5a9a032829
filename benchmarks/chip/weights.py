"""Random weights from the workload seed, made by the benchmark.

A family module's ``layout(m)`` says which leaves the system holds: global
leaves, and stacks of layers (a served name prefix such as ``blk/``, the
model's number of the stack's first layer, its depth, and each leaf's shape
and kind).  Each leaf of layer ``l`` has its own key,
``fold_in(fold_in(root, crc32(name)), l)`` with the leaf's plain name and
the model's layer number (0 for a global leaf), so the served weights (each
stack made on the device in one jitted call, in bf16) and the reference's
weights (one layer at a time, in float32) hold the same values without
either reading the other.  Stacks cover disjoint layers, so two stacks that
share a plain name still draw different values.

Matrices are N(0, 1/fan_in) with ``fan_in`` the second-to-last size, the
embedding N(0, 0.02²), biases N(0, 0.05²), norm scales 1 + N(0, 0.05²):
biases and scales that differ from 0 and 1 make a dropped bias or norm
weight show in the logits.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, ...]


class Stack(NamedTuple):
    prefix: str                       # the system's name prefix, e.g. "blk/"
    first: int                        # the model's number of its first layer
    layers: int
    leaves: Dict[str, Tuple[Shape, str]]  # plain name -> (shape, kind)


class Layout(NamedTuple):
    glob: Dict[str, Tuple[str, Shape, str]]  # plain name -> (system name, shape, kind)
    stacks: Tuple[Stack, ...]

    def kinds(self) -> Dict[str, str]:
        """Plain name -> kind, of the global leaves and of every stack's."""
        out = {n: kind for n, (_, _, kind) in self.glob.items()}
        for st in self.stacks:
            out.update({n: kind for n, (_, kind) in st.leaves.items()})
        return out

    def stack_of(self, layer: int) -> Stack:
        for st in self.stacks:
            if st.first <= layer < st.first + st.layers:
                return st
        raise IndexError(f"no stack holds layer {layer}")

    def served(self) -> Dict[str, Tuple[Shape, str]]:
        """The system's name -> (shape, dtype name) of every leaf."""
        want = {}
        for served, shape, _ in self.glob.values():
            want[served] = (tuple(shape), "bfloat16")
        spans = sorted((st.first, st.first + st.layers) for st in self.stacks)
        if any(b > c for (_, b), (c, _) in zip(spans, spans[1:])):
            raise ValueError(f"stacks overlap in layers {spans}: their "
                             f"leaves would share keys")
        for st in self.stacks:
            if set(st.leaves) & set(self.glob):
                raise ValueError(f"{st.prefix} leaves share a name with a "
                                 f"global leaf: their keys could meet")
            for n, (shape, _) in st.leaves.items():
                want[st.prefix + n] = ((st.layers,) + tuple(shape), "bfloat16")
        if len(want) != len(self.glob) + sum(len(st.leaves) for st in self.stacks):
            raise ValueError("two leaves have one system name")
        return want


def root_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (two 32-bit words)."""
    words = np.random.SeedSequence(int(seed) & (2**64 - 1)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def param_count(layout: Layout) -> int:
    """Parameters the system holds, padded vocabulary rows included."""
    return sum(int(np.prod(s)) for s, _ in layout.served().values())


def _leaf(key: jax.Array, name: str, layer, shape, kind: str) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        x = z * (1.0 / np.sqrt(shape[-2]))
    elif kind == "embed":
        x = z * 0.02
    elif kind == "bias":
        x = z * 0.05
    else:  # norm scale
        x = 1.0 + z * 0.05
    return x.astype(jnp.bfloat16)


def served_params(layout: Layout, key: jax.Array,
                  expected: Dict[str, Tuple[Shape, str]]
                  ) -> Dict[str, jax.Array]:
    """All weights in the system's layout, bf16, in one jitted call.

    ``expected`` maps each of the system's parameter names to (shape, dtype
    name); any difference in names, shapes or dtype is an error."""
    want = layout.served()
    if want != expected:
        missing = sorted(set(expected) - set(want))
        extra = sorted(set(want) - set(expected))
        differ = sorted(k for k in set(want) & set(expected)
                        if want[k] != expected[k])
        raise ValueError(f"the system's parameters differ from the "
                         f"configuration's: not made here {missing}, not in "
                         f"the system {extra}, other shape or dtype {differ}")

    def make(key):
        out = {served: _leaf(key, n, 0, s, kind)
               for n, (served, s, kind) in layout.glob.items()}
        for st in layout.stacks:
            layers = jnp.arange(st.first, st.first + st.layers)
            for n, (s, kind) in st.leaves.items():
                out[st.prefix + n] = jax.vmap(
                    lambda l, n=n, s=s, kind=kind: _leaf(key, n, l, s, kind))(layers)
        return out

    return jax.jit(make)(key)


def reference_weights(layout: Layout, key: jax.Array
                      ) -> Tuple[Dict[str, jax.Array], Callable[[int], Dict[str, jax.Array]]]:
    """(global leaves, layer -> its stack's leaves), by plain name, float32
    holding the bf16 values."""
    glob = jax.jit(lambda k: {n: _leaf(k, n, 0, s, kind).astype(jnp.float32)
                              for n, (_, s, kind) in layout.glob.items()})(key)
    one = {st.prefix: jax.jit(
        lambda k, l, leaves=st.leaves: {
            n: _leaf(k, n, l, s, kind).astype(jnp.float32)
            for n, (s, kind) in leaves.items()})
        for st in layout.stacks}
    return glob, lambda layer: one[layout.stack_of(layer).prefix](
        key, jnp.int32(layer))
