"""Executors & execution policies (HPX P6 substrate).

C++17 parallel algorithms take an *execution policy*; HPX extends these
with *executors* that bind a policy to concrete execution resources, and a
resource partitioner that carves workers into named thread pools.  This
module is that surface:

**Executors** (where work runs) — all expose the HPX executor protocol
``post`` / ``async_execute`` / ``sync_execute`` / ``bulk_async_execute``:

- :class:`SequencedExecutor`   — inline, in the calling thread;
- :class:`ThreadPoolExecutor`  — a named pool of the resource partitioner
  (:meth:`repro.core.scheduler.Runtime.get_executor` hands these out);
- :class:`PriorityExecutor`    — wraps any executor with a scheduler
  priority (HPX ``annotating_executor`` / thread_priority);
- :class:`MeshExecutor`        — the device plane: data sharded over a mesh
  axis, bodies dispatched as sharded ``vmap``/``shard_map`` computations
  (TPU analogue of HPX distributed executors).

**Policies** (how algorithms lower) are *pure rewrite objects* — they carry
no resources of their own, only a lowering flavor plus executor/parameter
bindings:

    par.on(rt.get_executor("io"))              # bind to a resource
    par.with_(chunk_size=1024, priority=2)     # tune parameters
    par_task                                    # two-way: algorithms
                                                #   return Futures
    vec.on(MeshExecutor(mesh, "data"))         # device-plane lowering

Legacy spelling (``ExecutionPolicy(kind="mesh", mesh=..., axis=...)``,
``par.on(mesh)`` with a raw mesh) still works behind a thin deprecation
shim that rewrites it onto the executor hierarchy; :func:`mesh_policy` is
the supported convenience for ``vec.on(MeshExecutor(mesh, axis))``.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

from repro.core import scheduler as _sched
from repro.core.future import Future, make_exceptional_future, make_ready_future


# ------------------------------------------------------------------ executors
class Executor:
    """HPX executor protocol.

    ``plane`` distinguishes host executors (chunked Python bodies on a
    thread pool) from device executors (whole-array sharded dispatch).
    ``bulk_async_execute(fn, args_seq)`` launches one task per element of
    ``args_seq`` (a tuple element is splatted as ``fn(*elem)``) — the
    algorithms library lowers every parallel loop through it.
    """

    plane = "host"

    # -- submission core (subclasses implement) ---------------------------
    def _submit(self, fn: Callable[..., Any], args: Tuple[Any, ...],
                kwargs: dict, priority: Optional[int]) -> Future[Any]:
        raise NotImplementedError

    def _post(self, fn: Callable[..., Any], args: Tuple[Any, ...],
              kwargs: dict, priority: Optional[int]) -> None:
        """Fire-and-forget core.  Failures must stay loud: inline executors
        propagate, pool executors report via ``/scheduler{pool}/tasks/failed``
        — never an exception parked in a Future nobody reads."""
        fn(*args, **kwargs)

    @property
    def parallelism(self) -> int:
        """Concurrent tasks this executor can make progress on (chunking hint)."""
        return 1

    # -- HPX executor surface ---------------------------------------------
    def post(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> None:
        """Fire-and-forget (``hpx::post``)."""
        self._post(fn, args, kwargs, None)

    def async_execute(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> Future[Any]:
        """Schedule ``fn(*args, **kwargs)``; returns its Future (``hpx::async``)."""
        return self._submit(fn, args, kwargs, None)

    def sync_execute(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> Any:
        """Schedule and join (``hpx::sync``)."""
        return self.async_execute(fn, *args, **kwargs).get()

    def bulk_async_execute(self, fn: Callable[..., Any],
                           args_seq: Sequence[Any]) -> List[Future[Any]]:
        """One task per element; tuples splat as ``fn(*elem)``."""
        return [
            self._submit(fn, a if isinstance(a, tuple) else (a,), {}, None)
            for a in args_seq
        ]


class SequencedExecutor(Executor):
    """Runs everything inline in the calling thread (the ``seq`` resource).

    Futures it returns are already resolved — it exists so sequential and
    parallel lowerings share one code path in the algorithms library."""

    def _submit(self, fn, args, kwargs, priority):
        try:
            return make_ready_future(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001 — futures carry any error
            return make_exceptional_future(e)

    def sync_execute(self, fn, *args, **kwargs):
        return fn(*args, **kwargs)


class ThreadPoolExecutor(Executor):
    """Binds a *named* pool of the resource partitioner.

    The pool is resolved late — at submission, against ``runtime`` (or the
    global runtime when ``runtime`` is None) — so a module-level executor
    stays valid across runtime restarts.  ``fallback`` names a pool to use
    when the requested one was never partitioned (e.g. "io" consumers on a
    bare single-pool runtime)."""

    def __init__(self, pool: Optional[str] = None, *,
                 runtime: Optional["_sched.Runtime"] = None,
                 fallback: Optional[str] = None,
                 priority: Optional[int] = None):
        self.pool_name = pool
        self.fallback = fallback
        self.priority = priority
        self._runtime = runtime

    def _pool(self) -> "_sched.ThreadPool":
        rt = self._runtime if self._runtime is not None else _sched.get_runtime()
        return rt.pool(self.pool_name, fallback=self.fallback)

    @property
    def parallelism(self) -> int:
        return self._pool().num_workers

    def _submit(self, fn, args, kwargs, priority):
        prio = priority if priority is not None else self.priority
        return self._pool().spawn(
            fn, *args,
            priority=_sched.PRIORITY_NORMAL if prio is None else prio,
            **kwargs)

    def _post(self, fn, args, kwargs, priority):
        prio = priority if priority is not None else self.priority
        if args or kwargs:
            self._pool().spawn_raw(lambda: fn(*args, **kwargs), priority=prio)
        else:
            self._pool().spawn_raw(fn, priority=prio)

    def __repr__(self) -> str:
        return f"ThreadPoolExecutor({self.pool_name!r})"


class PriorityExecutor(Executor):
    """Wraps any executor, stamping a scheduler priority on its tasks
    (HPX ``thread_priority`` annotation).  Priority-oblivious executors
    (sequenced, mesh) run unchanged."""

    def __init__(self, inner: Executor, priority: int):
        self.inner = inner
        self.priority = priority

    @property
    def plane(self) -> str:  # type: ignore[override]
        return self.inner.plane

    @property
    def parallelism(self) -> int:
        return self.inner.parallelism

    def _submit(self, fn, args, kwargs, priority):
        return self.inner._submit(fn, args, kwargs,
                                  self.priority if priority is None else priority)

    def _post(self, fn, args, kwargs, priority):
        self.inner._post(fn, args, kwargs,
                         self.priority if priority is None else priority)

    def __repr__(self) -> str:
        return f"PriorityExecutor({self.inner!r}, priority={self.priority})"


class MeshExecutor(Executor):
    """Device-plane executor: data sharded over one mesh axis, algorithm
    bodies dispatched as sharded ``vmap`` / ``shard_map`` computations
    (the TPU analogue of an HPX distributed executor).

    Host-protocol calls (``post``/``async_execute``) run the Python callable
    inline — XLA dispatch is already asynchronous, so the host side of a
    device computation never needs a worker thread."""

    plane = "device"

    def __init__(self, mesh: Any, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis

    @property
    def parallelism(self) -> int:
        try:
            return int(self.mesh.shape[self.axis])
        except Exception:  # noqa: BLE001 — unknown mesh flavor
            return 1

    def _submit(self, fn, args, kwargs, priority):
        try:
            return make_ready_future(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001
            return make_exceptional_future(e)

    # -- device-plane dispatch (used by repro.core.algorithms) -------------
    def sharding(self):
        import jax

        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.axis))

    def put(self, arr):
        """Shard an array over the executor's mesh axis."""
        import jax

        return jax.device_put(arr, self.sharding())

    def vmap_apply(self, fn: Callable[[Any], Any], arr):
        """Elementwise map: sharded in, sharded out, body per element."""
        import jax

        return jax.jit(jax.vmap(fn), out_shardings=self.sharding())(self.put(arr))

    def sum_total(self, arr):
        """Global sum: per-shard partial + psum finish (collective)."""
        import jax
        import jax.numpy as jnp

        def _body(x):  # axis=0: elements may be batched arrays
            return jax.lax.psum(jnp.sum(x, axis=0), self.axis)

        return jax.jit(
            jax.shard_map(
                _body,
                mesh=self.mesh,
                in_specs=jax.sharding.PartitionSpec(self.axis),
                out_specs=jax.sharding.PartitionSpec(),
            )
        )(self.put(arr))

    def __repr__(self) -> str:
        return f"MeshExecutor(axis={self.axis!r}, mesh={self.mesh!r})"


def get_executor(pool: Optional[str] = None, priority: Optional[int] = None,
                 fallback: Optional[str] = None,
                 runtime: Optional["_sched.Runtime"] = None) -> Executor:
    """Executor over a named pool of the resource partitioner.

    This (via ``Runtime.get_executor``) is the sanctioned way for code
    outside :mod:`repro.core` to reach scheduler pools."""
    ex: Executor = ThreadPoolExecutor(pool, runtime=runtime, fallback=fallback)
    if priority is not None:
        ex = PriorityExecutor(ex, priority)
    return ex


# ------------------------------------------------------------------- policies
_FLAVORS = ("seq", "par", "vec")


def _warn_legacy(msg: str) -> None:
    warnings.warn(msg, DeprecationWarning, stacklevel=3)


class ExecutionPolicy:
    """A pure rewrite object: lowering flavor + executor/parameter bindings.

    - ``flavor``     "seq" (inline loop), "par" (chunked over an executor's
      pool), "vec" (vectorized via ``jax.vmap`` / jnp);
    - ``executor``   where chunks/arrays go (None → seq inline, par default
      pool; a device-plane executor switches any flavor to sharded array
      lowering);
    - ``chunk_size`` / ``priority``  executor parameters (``with_``);
    - ``task``       two-way execution: algorithms return ``Future``s
      instead of joining (HPX ``par(task)``).
    """

    __slots__ = ("flavor", "executor", "chunk_size", "priority", "task")

    def __init__(self, flavor: Optional[str] = None, chunk_size: Optional[int] = None,
                 mesh: Any = None, axis: Optional[str] = None, *,
                 kind: Optional[str] = None,
                 executor: Optional[Executor] = None,
                 priority: Optional[int] = None, task: bool = False):
        if kind is not None:  # legacy keyword spelling
            _warn_legacy(
                "ExecutionPolicy(kind=...) is deprecated; use the policy "
                "objects (seq/par/vec/par_task) with .on(executor)/.with_()")
            flavor = flavor or kind
        if flavor == "mesh" or mesh is not None:  # legacy device-plane spelling
            _warn_legacy(
                "ExecutionPolicy('mesh', mesh=..., axis=...) is deprecated; "
                "use vec.on(MeshExecutor(mesh, axis))")
            if mesh is None:
                raise ValueError("mesh policy requires a mesh")
            executor = MeshExecutor(mesh, axis or "data")
            flavor = "vec"
        flavor = flavor or "seq"
        if flavor not in _FLAVORS:
            raise ValueError(f"unknown policy flavor {flavor!r}; choose from {_FLAVORS}")
        object.__setattr__(self, "flavor", flavor)
        object.__setattr__(self, "executor", executor)
        object.__setattr__(self, "chunk_size", None if chunk_size is None else int(chunk_size))
        object.__setattr__(self, "priority", priority)
        object.__setattr__(self, "task", bool(task))

    def __setattr__(self, name: str, value: Any) -> None:
        raise AttributeError("ExecutionPolicy is immutable; use .on()/.with_()")

    def _replace(self, **kw: Any) -> "ExecutionPolicy":
        cur = {s: getattr(self, s) for s in self.__slots__}
        cur.update(kw)
        return ExecutionPolicy(cur["flavor"], chunk_size=cur["chunk_size"],
                               executor=cur["executor"],
                               priority=cur["priority"], task=cur["task"])

    # -- rewrites ---------------------------------------------------------
    def on(self, executor: Any, axis: str = "data") -> "ExecutionPolicy":
        """Bind to an executor (HPX ``policy.on(exec)``).

        Legacy: a raw ``jax.sharding.Mesh`` is accepted and wrapped in a
        :class:`MeshExecutor` with a deprecation warning."""
        if not isinstance(executor, Executor):
            _warn_legacy(
                "policy.on(mesh) with a raw mesh is deprecated; pass "
                "MeshExecutor(mesh, axis)")
            executor = MeshExecutor(executor, axis)
        return self._replace(executor=executor)

    def with_(self, chunk_size: Optional[int] = None,
              priority: Optional[int] = None,
              task: Optional[bool] = None) -> "ExecutionPolicy":
        """Rebind executor parameters (HPX ``policy.with_(params)``)."""
        kw: dict = {}
        if chunk_size is not None:
            kw["chunk_size"] = int(chunk_size)
        if priority is not None:
            kw["priority"] = priority
        if task is not None:
            kw["task"] = bool(task)
        return self._replace(**kw)

    def with_chunk_size(self, n: int) -> "ExecutionPolicy":
        """Back-compat alias for ``with_(chunk_size=n)``."""
        return self.with_(chunk_size=n)

    # -- legacy readers ---------------------------------------------------
    @property
    def kind(self) -> str:
        """Legacy tag: "mesh" when bound to a device-plane executor."""
        if self.executor is not None and self.executor.plane == "device":
            return "mesh"
        return self.flavor

    @property
    def mesh(self) -> Any:
        ex = self.executor
        return getattr(ex, "mesh", None)

    @property
    def axis(self) -> Optional[str]:
        ex = self.executor
        return getattr(ex, "axis", None)

    def __repr__(self) -> str:
        bits = [self.flavor]
        if self.task:
            bits.append("task")
        if self.executor is not None:
            bits.append(f"on={self.executor!r}")
        if self.chunk_size is not None:
            bits.append(f"chunk_size={self.chunk_size}")
        if self.priority is not None:
            bits.append(f"priority={self.priority}")
        return f"ExecutionPolicy({', '.join(bits)})"

    def __eq__(self, other: Any) -> bool:
        return (isinstance(other, ExecutionPolicy)
                and all(getattr(self, s) == getattr(other, s) for s in self.__slots__))

    def __hash__(self) -> int:
        return hash((self.flavor, id(self.executor), self.chunk_size,
                     self.priority, self.task))


seq = ExecutionPolicy("seq")
par = ExecutionPolicy("par")
vec = ExecutionPolicy("vec")
seq_task = ExecutionPolicy("seq", task=True)
par_task = ExecutionPolicy("par", task=True)  # HPX par(task): two-way algorithms


def mesh_policy(mesh: Any, axis: str = "data") -> ExecutionPolicy:
    """Device-plane policy: ``vec`` lowered through a :class:`MeshExecutor`."""
    return vec._replace(executor=MeshExecutor(mesh, axis))
