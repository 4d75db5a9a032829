"""The system under test, as the benchmark drives it.

The one module of the benchmark that imports the system (``repro``).  It
builds the model named by a configuration file's ``system`` section,
checks through the file's family module (``families/``) that its model is
the file's, hands it the benchmark's weights, and serves through
``Router.replicate(model, params, ServeConfig, 1)`` → ``Engine``, the path
users call.  It touches only the system's public
surface: ``Router.submit`` with a token stream, the serve counters, and
``Engine.pause`` after the window.  The warm-up is a request at every
prompt length the traffic reaches.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import manifest
import weights
from model_spec import ModelSpec
from traffic_common import Request, host_rng, token_ids

ENGINE = "engine#0"


class TokenStream:
    """Channel-alike the engine streams tokens into; stamps each token."""

    __slots__ = ("req", "_closed")

    def __init__(self, req: Request):
        self.req = req
        self._closed = False

    def set(self, tok: int) -> None:
        self.req.token_t.append(time.perf_counter())
        self.req.tokens.append(int(tok))

    def close(self, exc: Optional[BaseException] = None) -> None:
        self._closed = True

    def is_closed(self) -> bool:
        return self._closed


class System:
    def __init__(self, conf: dict, m: ModelSpec, seed: int,
                 fault: Optional[Callable] = None):
        """``fault(model)``, where given, breaks the timed path before the
        engine is built (``faults.py``; never in the benchmark's runs)."""
        from repro.configs import get_config
        from repro.dist.plan import get_plan
        from repro.models.model import build_model
        from repro.serve.engine import ServeConfig
        from repro.serve.router import Router

        sysc = conf["system"]
        cfg = dataclasses.replace(get_config(sysc["arch"],
                                             smoke=sysc.get("smoke", False)),
                                  **sysc["overrides"])
        fam = manifest.family(m.model_type)
        fam.check_system(cfg, m, sysc["serve"]["cache_len"])
        self.m = m
        self.model = build_model(cfg, get_plan("serve"))
        specs = self.model.param_specs()
        expected = {n: (tuple(s.shape), jnp.dtype(s.dtype).name)
                    for n, s in specs.items()}
        params = jax.block_until_ready(
            weights.served_params(fam.layout(m), weights.root_key(seed),
                                  expected))
        if fault is not None:
            fault(self.model)
        self.scfg = ServeConfig(**sysc["serve"], eos_id=-1)
        self.router = Router.replicate(self.model, params, self.scfg, 1)

    # ------------------------------------------------------------- serving
    def submit(self, r: Request) -> None:
        from repro.serve.engine import GREEDY

        fut = self.router.submit(r.prompt, r.max_new, GREEDY,
                                 stream=TokenStream(r))

        def done(f) -> None:
            exc = f.exception()
            if exc is not None:
                r.failed = repr(exc)
            r.done_t = time.perf_counter()

        fut.on_ready(done)

    def counters(self) -> Dict[str, float]:
        from repro.core import counters

        reg = counters.default()
        steps = reg.get(f"/serve{{{ENGINE}}}/step/duration").stats()["count"]
        return {"steps": float(steps)}

    def pages(self) -> Tuple[float, float]:
        from repro.core import counters

        reg = counters.default()
        return (reg.get(f"/serve{{{ENGINE}}}/pages/in_use").get_value(),
                reg.get(f"/serve{{{ENGINE}}}/pages/capacity").get_value())

    # -------------------------------------------------------------- warm-up
    def warm_up(self, lengths, seed: int) -> List[Request]:
        """Submit one request of three tokens at each of ``lengths``: every
        prefill bucket, every page count of the admission and the decode
        step that the traffic reaches compile (or load) while they are
        served.  They go ahead of the traffic in the queue."""
        rng = host_rng(seed, 9)
        warm = [Request(-1 - i, token_ids(rng, n, self.m.vocab), 2, 0.0)
                for i, n in enumerate(lengths)]
        for r in warm:
            self.submit(r)
        return warm

    # ---------------------------------------------------------------- close
    def close(self) -> None:
        """Stop the engine at a step boundary and let go of its weights and
        page pool.  The engine itself stays reachable (the counter registry
        holds callables on it), so the arrays are released by name, where
        the engine has them; where it does not, they stay, and the
        reference still fits beside them."""
        eng = self.router.engines[0]
        eng.pause(timeout=120)
        self.router.remove_engine(ENGINE)
        eng.params = None
        pools = getattr(getattr(getattr(eng, "backend", None), "kv", None),
                        "pools", None)
        if isinstance(pools, dict):
            pools.clear()
        self.router = self.model = None
