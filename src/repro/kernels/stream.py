"""STREAM triad kernel (TPU Pallas): out = a + α·b.

Reproduces the paper's HPX.Compute claim — "porting STREAM to the
single-source abstraction results in no loss of performance" — at the
Pallas layer: the kernel is pure bandwidth, so parity with the native jnp
expression (one fused multiply-add over HBM) is the pass criterion
(``tests/test_kernels.py::test_stream_triad_sweep`` checks the parity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _triad_kernel(a_ref, b_ref, o_ref, *, alpha: float):
    o_ref[...] = a_ref[...] + alpha * b_ref[...]


def triad(a: jax.Array, b: jax.Array, alpha: float = 3.0, *,
          block: int = 65536, interpret: bool = False) -> jax.Array:
    """a/b: (N,) → a + α·b, blocked through VMEM. N % block == 0."""
    (N,) = a.shape
    assert N % block == 0, (N, block)
    kernel = functools.partial(_triad_kernel, alpha=alpha)
    return pl.pallas_call(
        kernel,
        grid=(N // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, b)
