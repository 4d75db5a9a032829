"""Pod-axis manual collectives (DESIGN.md §2, §5).

GSPMD derives every *intra-pod* collective from the sharding plan; the
*inter-pod* (DCI) hop is the one place we drop to manual control, because
it is the slow wire and the one worth compressing.  The tools here:

- :func:`pod_manual_value_and_grad` — a partial-manual ``shard_map`` over
  the ``pod`` mesh axis: each pod runs the (GSPMD-auto) backward on its
  batch shard, then gradients cross the DCI as **bf16** via an explicit
  ``psum`` — half the wire bytes of the fp32 reduction XLA would emit.
- :func:`make_error_feedback` — unbiased error-feedback compression for
  a gradient stream whose quantization point the caller controls (e.g.
  microbatch accumulation before the reduction): the quantization
  residual is carried to the next step, so the *sum* of compressed
  gradients equals the true sum exactly
  (``tests/test_train.py::test_error_feedback_unbiased_over_steps``).
- :func:`all_gather_tree` — explicit pod-axis all-gather (metrics /
  debugging inside manual regions).

The 512-device CPU emulation of the compressed path crashes inside XLA
(tracked in EXPERIMENTS §Perf); TPU is the target, and the unit tests pin
the math on a 1×1 host mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _pod_axis(mesh: Any) -> str:
    """The inter-pod mesh axis; falls back to the leading axis on meshes
    without an explicit ``pod`` dimension (single-pod test meshes)."""
    return "pod" if "pod" in mesh.axis_names else mesh.axis_names[0]


def pod_manual_value_and_grad(loss_fn: Callable, mesh: Any,
                              compress: bool = True) -> Callable:
    """``value_and_grad(loss_fn)`` with a manual pod-axis reduction.

    Returns ``f(params, batch) -> (loss, grads)``.  ``batch`` leaves are
    sharded over the pod axis (dim 0); ``params`` are replicated across
    pods (each pod holds its FSDP/TP shard under the *auto* axes, which
    stay GSPMD-managed — this is a partial-manual ``shard_map``).  With
    ``compress=True`` gradients ride the DCI as bf16 — the ring sum itself
    runs at wire precision (that is the bandwidth win); only the final
    mean/cast back to the param dtype is fp32.  The per-step rounding here
    is NOT error-corrected: :func:`make_error_feedback` is the primitive
    for callers that own a quantization point outside the reduction (e.g.
    a grad-accumulation stream) and can carry its residual across steps.
    """
    axis = _pod_axis(mesh)
    n_pods = dict(mesh.shape)[axis]

    def vg(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # equal-size pod shards ⇒ global mean = mean of pod means
        loss = jax.lax.psum(loss, axis) / n_pods

        def reduce_grad(g: jax.Array) -> jax.Array:
            if compress:
                wire = g.astype(jnp.bfloat16)           # half-width DCI hop
                total = jax.lax.psum(wire, axis)
                return (total.astype(jnp.float32) / n_pods).astype(g.dtype)
            return jax.lax.psum(g, axis) / n_pods

        return loss, jax.tree.map(reduce_grad, grads)

    return jax.shard_map(vg, mesh=mesh,
                         in_specs=(P(), P(axis)),
                         out_specs=(P(), P()),
                         axis_names={axis}, check_vma=False)


def all_gather_tree(tree: Any, mesh: Any, axis: str | None = None,
                    tiled: bool = False) -> Any:
    """Explicit pod-axis all-gather of a pytree (manual-region utility).

    Rank-0 leaves (per-pod scalar metrics) are replicated in and gathered
    into a ``(n_pods,)`` vector; array leaves are sharded on dim 0."""
    axis = axis or _pod_axis(mesh)
    in_specs = jax.tree.map(
        lambda x: P(axis) if jnp.ndim(x) > 0 else P(), tree)

    def gather(t):
        return jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, tiled=tiled and jnp.ndim(x) > 0),
            t)

    # partial-auto shard_map only has a jit lowering (no eager impl)
    return jax.jit(jax.shard_map(gather, mesh=mesh, in_specs=(in_specs,),
                                 out_specs=P(), axis_names={axis},
                                 check_vma=False))(tree)


# ------------------------------------------------------- error feedback
def make_error_feedback(wire_dtype: Any = jnp.bfloat16
                        ) -> Tuple[Callable, Callable]:
    """Unbiased error-feedback compression for a gradient stream.

    Returns ``(init, compress)``:

        residual = init(grads_like)            # zeros, fp32
        q, residual = compress(grads, residual)

    Each step quantizes ``grads + residual`` to ``wire_dtype`` and carries
    the rounding error forward.  Telescoping makes the stream exact:
    ``Σ dequant(q_t) + residual_T == Σ g_t`` (the bf16 rounding error of
    step *t* is re-injected at step *t+1*, so drift stays bounded at the
    wire dtype's ulp instead of growing with the horizon).
    """

    def init(grads: Any) -> Any:
        return jax.tree.map(
            lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)

    def compress(grads: Any, residual: Any) -> Tuple[Any, Any]:
        carried = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + e, grads, residual)
        q = jax.tree.map(lambda s: s.astype(wire_dtype), carried)
        new_residual = jax.tree.map(
            lambda s, qq: s - qq.astype(jnp.float32), carried, q)
        return q, new_residual

    return init, compress
