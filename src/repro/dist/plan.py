"""Sharding plans: logical-axis → mesh-axis resolution (DESIGN.md §2).

A :class:`ShardingPlan` is the *whole* distribution strategy of a step —
which mesh axis every logical tensor axis lands on, where the gather point
sits (bulk/BSP vs per-layer/futurized), the remat policy, and the collective
dtype boundaries.  Models never name mesh axes: they constrain activations
and declare parameters by **logical** axes (``embed``, ``mlp``, ``kv_seq``,
…, see ``models/params.py``) and the plan resolves them against whatever
mesh is active.  That indirection is what lets the same model run under the
paper's BSP baseline and the futurized/optimized AMT schedules unchanged.

Resolution rules (exercised by ``tests/test_plan.py``):

- **FCFS mesh-axis allocation** — axes are resolved left-to-right and each
  mesh axis is used at most once per spec; a logical axis whose mesh axis
  was already consumed replicates instead.  (``("experts","embed","mlp")``
  with experts and mlp both → ``model``: experts wins, mlp replicates.)
- **divisibility guard** — a dim that the assigned mesh axes do not divide
  falls back toward replication (axes are dropped right-to-left until the
  product divides), so odd vocab/head counts never wedge GSPMD.
- **trailing-``None`` trimming** — specs are canonicalized by dropping
  trailing replicated entries (``P("model","data",None)`` → ``P("model",
  "data")``).

The registry (``get_plan``) holds the four production plans:

    bsp        gather-upfront, full remat, no FSDP — the barrier-heavy
               MPI+X baseline of the paper
    futurized  FSDP with per-layer gather/reduce-scatter inside the scan —
               the AMT analogue (overlap via async collectives)
    optimized  futurized + KV/seq sharding + bf16 collective boundaries +
               selective remat (beyond-paper, EXPERIMENTS.md §Perf)
    serve      TP-only inference plan: weights whole per shard, KV cache
               sequence-sharded over the model axis
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# A rule value: mesh-axis name, preference-ordered tuple of mesh axes (the
# dim is sharded over every present one jointly), or None (replicate).
Rule = Union[str, Tuple[str, ...], None]


def _active_mesh() -> Optional[Any]:
    """The ambient mesh (``jax.set_mesh`` / legacy ``with mesh:``), or None.

    Used by :meth:`ShardingPlan.constrain` and by grouped-local MoE dispatch
    (``models/moe.py``) — model code runs unchanged on bare CPU (no mesh →
    constraints are no-ops) and on production meshes.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh
    from jax.interpreters import pxla  # legacy ``with mesh:`` resource env

    phys = pxla.thread_resources.env.physical_mesh
    return None if phys.empty else phys


def _mesh_sizes(mesh: Any) -> Dict[str, int]:
    """{axis name: size} for a concrete Mesh or an AbstractMesh."""
    return dict(mesh.shape)


@dataclass(frozen=True)
class ShardingPlan:
    """A named distribution strategy; immutable (ablate with
    ``dataclasses.replace``, see ``launch/dryrun.py`` variants)."""

    name: str
    rules: Dict[str, Rule] = field(default_factory=dict)
    fsdp: bool = True                  # params sharded over the data axis
    gather_upfront: bool = False       # BSP: bulk all-gather before the scan
    remat_policy: str = "none"         # none | dots | full
    bf16_boundaries: bool = False      # bf16 cotangents at collective edges
    compress_pod_grads: bool = False   # pod-axis bf16 gradient reduction
    microbatches: int = 1              # grad-accumulation chunks

    # ------------------------------------------------------------- resolve
    def spec(self, axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Any) -> P:
        """Resolve logical ``axes`` for a tensor of ``shape`` on ``mesh``.

        FCFS over mesh axes, divisibility-guarded, trailing-None trimmed.
        ``mesh`` may be a concrete ``Mesh`` or an ``AbstractMesh`` (the
        dry-run resolves specs before any device exists).
        """
        assert len(axes) == len(shape), (axes, shape)
        sizes = _mesh_sizes(mesh)
        used: set = set()
        entries: list = []
        for ax, dim in zip(axes, shape):
            assigned: list = []
            for cand in self._candidates(ax):
                if cand in sizes and cand not in used and cand not in assigned:
                    assigned.append(cand)
            # divisibility guard: drop axes (least-preferred first) until
            # the joint degree divides the dim; empty ⇒ replicate
            while assigned and dim % math.prod(sizes[a] for a in assigned):
                assigned.pop()
            if assigned:
                used.update(assigned)
                entries.append(assigned[0] if len(assigned) == 1
                               else tuple(assigned))
            else:
                entries.append(None)
        while entries and entries[-1] is None:  # canonical trailing trim
            entries.pop()
        return P(*entries)

    def _candidates(self, ax: Optional[str]) -> Tuple[str, ...]:
        if ax is None:
            return ()
        rule = self.rules.get(ax)
        if rule is None:
            return ()
        if isinstance(rule, str):
            return (rule,)
        return tuple(rule)

    # ----------------------------------------------------------- shardings
    def sharding(self, axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Any) -> NamedSharding:
        return NamedSharding(mesh, self.spec(axes, shape, mesh))

    def replicated(self, mesh: Any) -> NamedSharding:
        return NamedSharding(mesh, P())

    def param_shardings(self, specs: Mapping[str, Any], mesh: Any
                        ) -> Dict[str, NamedSharding]:
        """Sharding pytree for a ``{path: ParamSpec}`` dict (one source of
        truth: the spec's logical axes)."""
        return {p: self.sharding(s.axes, s.shape, mesh)
                for p, s in specs.items()}

    def sharding_for(self, leaf: Any, mesh: Optional[Any] = None) -> P:
        """Spec for a path-free leaf (elastic migration of opaque pytrees,
        ``core/migration.py``): batch-shard dim 0 over the data axes when
        divisible, otherwise replicate.

        Pass the TARGET mesh explicitly when migrating
        (``lambda l: plan.sharding_for(l, new_mesh)``): the divisibility
        guard must run against the destination's axis sizes, and the
        ambient-mesh fallback may still be the source mesh."""
        mesh = mesh if mesh is not None else _active_mesh()
        shape = getattr(leaf, "shape", ())
        if mesh is None or not shape:
            return P()
        return self.spec(("batch",) + (None,) * (len(shape) - 1), shape, mesh)

    # ----------------------------------------------------------- constrain
    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]
                  ) -> jax.Array:
        """``with_sharding_constraint`` against the active mesh; identity
        when no mesh is set (single-host tests / CPU smoke runs)."""
        mesh = _active_mesh()
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.sharding(axes, x.shape, mesh))


# ---------------------------------------------------------------- registry
def _tp_rules(**overrides: Rule) -> Dict[str, Rule]:
    """The shared tensor-parallel core every plan builds on."""
    rules: Dict[str, Rule] = {
        # -------- parameters (logical axes from models/params.py)
        "embed": "data",          # FSDP axis (overridden off for bsp/serve)
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",       # EP rides the model axis
        "ssm_inner": "model",
        "lru": "model",
        # "layers" is never sharded: absent ⇒ replicate
        # -------- activations
        "batch": ("pod", "data"),
        "seq": None,              # gathered for attention
        "seq_sp": None,           # sequence-parallel residual stream
        "kv_seq": None,           # decode-time KV cache sequence dim
        "expert_cap": None,
    }
    rules.update(overrides)
    return rules


def bsp_plan(**overrides: Any) -> ShardingPlan:
    """The paper's baseline: bulk-synchronous steps — params gathered
    up-front (one global barrier), full remat, gradients reduced at the
    end.  TP still applies (the baseline is MPI+X, not single-chip)."""
    return replace(ShardingPlan(
        name="bsp",
        rules=_tp_rules(embed=None),
        fsdp=False,
        gather_upfront=True,
        remat_policy="full",
    ), **overrides)


def futurized_plan(**overrides: Any) -> ShardingPlan:
    """The AMT analogue: FSDP over ``data``, per-layer gather inside the
    scan, per-layer reduce-scatter in backward — XLA overlaps the async
    collectives with compute exactly like an HPX dataflow graph."""
    return replace(ShardingPlan(
        name="futurized",
        rules=_tp_rules(),
        fsdp=True,
        gather_upfront=False,
        remat_policy="none",
    ), **overrides)


def optimized_plan(**overrides: Any) -> ShardingPlan:
    """Futurized + beyond-paper perf: KV-cache/sequence sharding over the
    model axis, bf16 collective boundaries, selective remat.  Pod-axis
    gradient compression stays off by default (XLA CPU crash at 512
    devices; see EXPERIMENTS §Perf — TPU is the target)."""
    return replace(ShardingPlan(
        name="optimized",
        rules=_tp_rules(kv_seq="model", seq_sp="model"),
        fsdp=True,
        gather_upfront=False,
        remat_policy="dots",
        bf16_boundaries=True,
        compress_pod_grads=False,
    ), **overrides)


def serve_plan(**overrides: Any) -> ShardingPlan:
    """Inference: TP-only (weights whole per shard — no per-step gathers to
    overlap at batch-1 latencies) + sequence-sharded KV cache, which makes
    GSPMD emit the flash-decoding partial-softmax combine."""
    return replace(ShardingPlan(
        name="serve",
        rules=_tp_rules(embed=None, kv_seq="model"),
        fsdp=False,
        gather_upfront=True,
        remat_policy="none",
    ), **overrides)


_REGISTRY = {
    "bsp": bsp_plan,
    "futurized": futurized_plan,
    "optimized": optimized_plan,
    "serve": serve_plan,
}


def get_plan(name: str, **overrides: Any) -> ShardingPlan:
    """Look up a plan by name; keyword overrides are applied with
    ``dataclasses.replace`` (e.g. ``get_plan("futurized",
    microbatches=4)``).  Raises ``KeyError`` for unknown names."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown plan {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**overrides)
