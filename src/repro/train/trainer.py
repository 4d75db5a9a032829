"""Futurized training driver (the end-to-end AMT loop).

The BSP trainer's step is: build batch → step → wait → maybe checkpoint —
every stage a barrier.  This driver futurizes all of it:

- batches are built by scheduler tasks ``prefetch`` steps ahead
  (``data.Prefetcher`` futures);
- the jitted step is dispatched asynchronously (JAX returns device futures;
  the host thread immediately starts the next iteration's admission);
- checkpoints are snapshotted and written by a scheduler task
  (``checkpoint.save_async``) while the device keeps training;
- the loop only synchronizes on metrics every ``log_every`` steps.

Fault tolerance: train state is AGAS-registered (GID stable across
migrations); ``elastic_restart`` reshards the live state onto a new mesh
(node-failure shrink / expansion), and ``Trainer.resume`` restores the
latest checkpoint onto whatever mesh is active.  Straggler detection: the
step-time EMA counter flags steps > ``straggler_factor``× EMA and counts
them (``/train{loop#0}/stragglers/detected``) — the policy hook
re-dispatches the batch (host-level retry) when enabled.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.core import agas as _agas
from repro.core import counters as _counters
from repro.core import migration
from repro.core import scheduler as _sched
from repro.core.future import Future
from repro.data.pipeline import DataConfig, Prefetcher
from repro.models.model import Model
from repro.obs import trace as _trace
from repro.optim import adamw
from repro.train import step as step_mod


@dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0  # 0 = disabled
    ckpt_dir: str = "checkpoints"
    straggler_factor: float = 3.0
    retry_stragglers: bool = False


class Trainer:
    def __init__(self, model: Model, opt_cfg: adamw.AdamWConfig,
                 data_cfg: DataConfig, tcfg: TrainConfig,
                 mesh=None, rng_seed: int = 0, prefetcher=None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        # Ensure the AMT runtime is up and the I/O plane is partitioned:
        # prefetch assembly and checkpoint writes run on the "io" pool.
        _sched.get_runtime().add_pool("io", 1)

        key = jax.random.PRNGKey(rng_seed)
        if mesh is None:
            self.params = model.init(key)
            self.opt_state = adamw.init(self.params)
        else:
            # state is created already split over the mesh by the plan's
            # shardings — never whole on one chip first
            p_sh, o_sh = step_mod.train_state_shardings(model, mesh)
            self.params = jax.jit(model.init, out_shardings=p_sh)(key)
            self.opt_state = jax.jit(adamw.init, out_shardings=o_sh)(self.params)
        self.step_num = 0
        self._step_fn = self._jit_step(mesh)
        # Any ``get(step) -> Future[batch]`` source plugs in — notably
        # ``data.pipeline.LocalShardFeeder`` (locality-sharded datasets:
        # this trainer then feeds exclusively from segments its own
        # locality holds, the work-to-data training path).
        self.prefetcher = (prefetcher if prefetcher is not None
                           else Prefetcher(model.cfg, data_cfg))
        self.gid = _agas.default().register_name(
            f"/train/state/{model.cfg.name}",
            {"params": self.params, "opt": self.opt_state}, replace=True)

        reg = _counters.default()
        self.t_step = reg.timer("/train{loop#0}/step/duration",
                                percentiles=True)
        self.c_steps = reg.counter("/train{loop#0}/steps/cumulative")
        self.c_straggler = reg.counter("/train{loop#0}/stragglers/detected")
        self.g_loss = reg.gauge("/train{loop#0}/loss/instantaneous")

    def _jit_step(self, mesh):
        """The jitted step.  On a mesh its outputs keep the plan's state
        shardings, each batch is split over the batch axes, and it is
        traced under the mesh, so the model's logical sharding constraints
        resolve against it."""
        step = step_mod.make_train_step(self.model, self.opt_cfg, mesh)
        if mesh is None:
            return jax.jit(step, donate_argnums=(0, 1))
        p_sh, o_sh = step_mod.train_state_shardings(self.model, mesh)
        jitted = jax.jit(step, donate_argnums=(0, 1),
                         out_shardings=(p_sh, o_sh,
                                        self.model.plan.replicated(mesh)))

        def run(params, opt_state, batch):
            batch = jax.device_put(
                batch, step_mod.batch_shardings(self.model, mesh, batch))
            with jax.set_mesh(mesh):
                return jitted(params, opt_state, batch)

        return run

    # ------------------------------------------------------------------ fit
    def fit(self, steps: Optional[int] = None) -> List[Dict[str, float]]:
        steps = steps or self.tcfg.steps
        history: List[Dict[str, float]] = []
        ckpt_futures: List[Future] = []
        for _ in range(steps):
            i = self.step_num
            batch = self.prefetcher.get(i).get()  # future → host batch
            t0 = time.perf_counter()
            with _trace.span("train/step", "train", step=i):
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch)
            if (i + 1) % self.tcfg.log_every == 0 or i + 1 == steps:
                loss = float(metrics["loss"])  # sync point (only here)
                dt = time.perf_counter() - t0
                self.t_step.add(dt)
                self._check_straggler(dt, batch)
                self.g_loss.set(loss)
                history.append({"step": i + 1, "loss": loss,
                                "grad_norm": float(metrics["grad_norm"])})
            self.c_steps.increment()
            self.step_num += 1
            if self.tcfg.ckpt_every and self.step_num % self.tcfg.ckpt_every == 0:
                ckpt_futures.append(self.checkpoint_async())
        for f in ckpt_futures:
            f.get()  # join outstanding checkpoint I/O
        _agas.default().rebind(self.gid, {"params": self.params, "opt": self.opt_state})
        return history

    def _check_straggler(self, dt: float, batch) -> None:
        ema = self.t_step.ema
        if ema is not None and dt > self.tcfg.straggler_factor * max(ema, 1e-9):
            self.c_straggler.increment()
            if self.tcfg.retry_stragglers:
                # host-level redundant dispatch: re-run the same batch (the
                # multi-controller analogue re-sends work to a healthy host)
                self.params, self.opt_state, _ = self._step_fn(
                    self.params, self.opt_state, batch)

    # ----------------------------------------------------------- checkpoint
    def checkpoint_async(self) -> Future:
        state = {"params": self.params, "opt": self.opt_state}
        return ckpt_mod.save_async(Path(self.tcfg.ckpt_dir), self.step_num, state)

    def resume(self, shardings: Optional[Any] = None) -> int:
        step, state = ckpt_mod.restore(Path(self.tcfg.ckpt_dir),
                                       shardings=shardings)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.step_num = step
        _agas.default().rebind(self.gid, state)
        return step

    # -------------------------------------------------------------- elastic
    def elastic_restart(self, new_mesh) -> None:
        """Migrate live state onto a different mesh (failure shrink / regrow)
        and rebuild the step function against it."""
        p_sh, o_sh = step_mod.train_state_shardings(self.model, new_mesh)
        self.params = migration.migrate_tree(self.params, p_sh)
        self.opt_state = migration.migrate_tree(self.opt_state, o_sh)
        self.mesh = new_mesh
        self._step_fn = self._jit_step(new_mesh)
        _agas.default().rebind(self.gid,
                               {"params": self.params, "opt": self.opt_state},
                               placement=new_mesh)
        _counters.counter("/train{loop#0}/elastic_restarts/cumulative").increment()
