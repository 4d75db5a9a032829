"""Serving engine: paged-KV continuous batching on the AMT runtime.

Task-pipelined, HPX-style: no prefill runs inside the decode loop, so an
admission never stalls the in-flight decodes.

1. **Admission** — ``submit`` enqueues the request and a prefill task is
   posted through a ``PriorityExecutor`` over the dedicated ``prefill``
   pool of the resource partitioner (falling back to the decode pool at
   ``PRIORITY_HIGH`` on unpartitioned runtimes), so admissions never steal
   decode-continuation slots.  Paged families right-pad prompts to static
   *buckets* so admission never recompiles; ``valid_len`` keeps
   logits/cache positions exact.  Finished prefills land in a ready queue.
2. **Decode continuation chain** — each step is a scheduler task that
   integrates ready prefills into free slots (paged: scatter the prefill
   KV into block-pool pages; dense fallback: migrate into the slot row),
   runs one jitted decode+sample step for the whole batch, streams each
   new token through the request's :class:`~repro.core.future.Channel`,
   and respawns itself.  No prefill barrier anywhere on the hot path.
3. **Completion** — EOS / length ends a slot: pages return to the free
   list, the future resolves with the token list, the stream closes.

Sampling (temperature / top-k / top-p) runs *inside* the jitted step with
per-slot parameter vectors — admission churn never changes shapes, so after
warmup the decode step never recompiles.  ``temperature=0`` rows reduce to
exact argmax (greedy equivalence).

Cache backends, chosen by the model family: block-pool paged KV
(:mod:`repro.serve.kv_cache`) for KV-cache families (dense/moe/vlm) —
memory ∝ live tokens, per-row lengths in the kernel — and a dense per-slot
cache for recurrent families (ssm/hybrid/encdec).

Performance counters: ``/serve{<name>}/requests/{submitted,completed}``,
``/serve{<name>}/tokens/generated``, ``/serve{<name>}/step/duration``,
``/serve{<name>}/request/{latency,first_token}``,
``/serve{<name>}/decode/pages_walked`` (paged: the pages the decode steps'
attention walks, per row the pages holding positions up to its new token),
plus the page-pool gauges from :mod:`repro.serve.kv_cache`.

Spans (:mod:`repro.obs.trace`, category ``serve``: JAX profiler
annotations always, ring events while the recorder is on).  On the
decode loop: ``admit`` (prefills launched and integrated; ``admitted``,
and ``ready_ms``, the longest KV-ready → slot-bound wait among them),
``decode_step`` (``step_num``, ``batch``; paged: ``pages_walked``, as the
counter counts it for the step) with its
children ``decode_step.dispatch`` (inputs uploaded, step enqueued) and
``decode_step.wait`` (the blocking token readback), then ``emit``
(tokens streamed, requests finished; ``finished``).  On the prefill
pool: ``prefill`` (``rid``, ``prompt_len``, ``bucket``, ``queued_ms``)
with its child ``prefill.wait`` (logits readback, first-token sampling).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agas as _agas
from repro.core import counters as _counters
from repro.core import executor as _executor
from repro.core.future import Channel, Future, Promise
from repro.core.scheduler import PRIORITY_HIGH, current_runtime
from repro.models.model import Model
from repro.obs import trace as _trace

_NEG = -1e30

# Admission: prefills in flight beyond free slots, and the blocking queue
# wait of a drained decode loop (no hot-spin).
_PREFILL_OVERSUB = 2
_IDLE_TIMEOUT = 0.05
# Resource partitioning: the decode continuation chain runs on
# ``_DECODE_POOL``; prefill tasks go to a PriorityExecutor over a dedicated
# ``_PREFILL_POOL`` (auto-partitioned with ``_PREFILL_WORKERS`` workers; on
# a runtime without one they fall back to the decode pool at PRIORITY_HIGH).
_DECODE_POOL = "default"
_PREFILL_POOL = "prefill"
_PREFILL_WORKERS = 2


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling controls. ``temperature=0`` → greedy (exact
    argmax, independent of top_k/top_p)."""
    temperature: float = 0.0
    top_k: int = 0      # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled


GREEDY = SamplingParams()


@dataclass
class ServeConfig:
    max_batch: int = 4
    cache_len: int = 256
    max_new_tokens: int = 32
    eos_id: int = -1  # -1: never stops early
    page_size: int = 16  # paged cache: tokens per block-pool page
    # Counters are get-or-create by name: same-named engines *share* them
    # (the seed's observability contract).  Replicas behind a Router must
    # use distinct names or load() merges — Router.replicate does this.
    name: str = "engine#0"
    seed: int = 0


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new: int
    promise: Promise
    sampling: SamplingParams
    stream: Optional[Channel]
    generated: List[int] = field(default_factory=list)
    submit_t: float = 0.0
    ready_t: float = 0.0  # prefill done: KV and first token ready
    first_token_t: float = 0.0
    # opaque picklable routing info (fleet relay: client locality, stream
    # id) that survives live migration — the destination re-attaches its
    # stream and completion hooks from this
    meta: Optional[Dict[str, Any]] = None
    # fleet-global request tag ("r<loc>:<seq>" from the router, or a local
    # fallback) stamped into every span — the critical-path join key
    tag: str = ""


def _cache_batch_axis(name: str) -> int:
    return 0 if name == "pos" else 1


def sample_logits(logits: jax.Array, key: jax.Array, temp: jax.Array,
                  topk: jax.Array, topp: jax.Array) -> jax.Array:
    """Batched sampling, jit-safe with *per-row dynamic* controls.

    logits: (B, V) fp32; temp/topp: (B,) fp32; topk: (B,) int32 (0 = off).
    Rows with temp <= 0 return exact argmax.  top-k/top-p masks are
    derived in sorted space (kth value / nucleus cutoff), so k and p vary
    per row without shape changes → zero recompiles across admissions.
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        t = jnp.where(temp > 0, temp, 1.0).astype(jnp.float32)
        lg = logits.astype(jnp.float32) / t[:, None]
        srt = jnp.sort(lg, axis=-1)[:, ::-1]  # descending
        k_eff = jnp.where(topk > 0, topk, V).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, jnp.clip(k_eff[:, None] - 1, 0, V - 1),
                                  axis=-1)  # (B, 1) value of the k-th logit
        lg = jnp.where(lg < kth, _NEG, lg)
        # nucleus: smallest sorted prefix with mass ≥ top_p (in the top-k set)
        srt_k = jnp.where(jnp.arange(V)[None, :] < k_eff[:, None], srt, _NEG)
        p_srt = jax.nn.softmax(srt_k, axis=-1)
        excl = jnp.cumsum(p_srt, axis=-1) - p_srt
        ncut = jnp.maximum(jnp.sum((excl < topp[:, None]).astype(jnp.int32),
                                   axis=-1), 1)
        cutoff = jnp.take_along_axis(srt_k, (ncut - 1)[:, None], axis=-1)
        lg = jnp.where(lg < cutoff, _NEG, lg)
        g = jax.random.gumbel(key, lg.shape, jnp.float32)
        samp = jnp.argmax(lg + g, axis=-1).astype(jnp.int32)
        return jnp.where(temp <= 0, greedy, samp)

    # all-greedy batches (the common serving default) skip the sort entirely;
    # lax.cond keeps it one compile either way
    return jax.lax.cond(jnp.any(temp > 0), _sampled, lambda _: greedy, None)


def _sample_host(logits: np.ndarray, sp: SamplingParams,
                 rng: np.random.Generator) -> int:
    """Host-side mirror of :func:`sample_logits` for the B=1 prefill token."""
    if sp.temperature <= 0:
        return int(np.argmax(logits))
    lg = logits.astype(np.float64) / sp.temperature
    srt = np.sort(lg)[::-1]
    if sp.top_k > 0:
        lg = np.where(lg < srt[min(sp.top_k, lg.size) - 1], _NEG, lg)
        srt = np.where(np.arange(srt.size) < sp.top_k, srt, _NEG)
    p = np.exp(srt - srt.max())
    p /= p.sum()
    excl = np.cumsum(p) - p
    ncut = max(int((excl < sp.top_p).sum()), 1)
    lg = np.where(lg < srt[ncut - 1], _NEG, lg)
    return int(np.argmax(lg + rng.gumbel(size=lg.shape)))


# --------------------------------------------------------------- backends
class _DenseSlots:
    """Dense per-slot cache, (L, max_batch, cache_len, ...): the backend of
    the families without paged support (ssm/hybrid/encdec)."""

    def __init__(self, model: Model, scfg: ServeConfig,
                 extra: Dict[str, Any]):
        specs = model.cache_specs(scfg.max_batch, scfg.cache_len,
                                  enc_len=extra.get("enc_len"))
        self.cache = {k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()}
        self.gid = _agas.default().register(self.cache, name=None,
                                            placement="host-engine")

    def admit(self, slot: int, prefill_cache: Dict[str, jax.Array],
              length: int) -> bool:
        # self.cache is the AGAS-registered dict: update keys in place so
        # the global view stays current (and the zero-init cache is freed)
        self.cache.update({
            k: v.at[(slice(None), slot) if _cache_batch_axis(k) == 1 else slot].set(
                jnp.take(prefill_cache[k], 0, axis=_cache_batch_axis(k)))
            for k, v in self.cache.items()
        })
        return True

    def prepare_step(self, slot: int) -> bool:
        return True

    def release(self, slot: int) -> None:
        pass

    def device_cache(self) -> Dict[str, jax.Array]:
        return self.cache

    def commit(self, new_cache: Dict[str, jax.Array]) -> None:
        self.cache.update(new_cache)

    def step_bookkeeping(self, active: List[int]) -> None:
        pass

    def snapshot_slot(self, slot: int) -> Dict[str, Any]:
        raise NotImplementedError(
            "dense cache backend does not support live migration — "
            "only paged (KV-cache) families migrate")

    def restore_slot(self, slot: int, snap: Dict[str, Any]) -> bool:
        raise NotImplementedError(
            "dense cache backend does not support live migration — "
            "only paged (KV-cache) families migrate")


class _PagedSlots:
    """Block-pool paged cache backend (see :mod:`repro.serve.kv_cache`)."""

    def __init__(self, model: Model, scfg: ServeConfig):
        from repro.serve.kv_cache import PagedKVCache

        page = scfg.page_size
        assert scfg.cache_len % page == 0, (scfg.cache_len, page)
        maxp = scfg.cache_len // page
        # every slot can reach cache_len, plus the scratch page
        self.kv = PagedKVCache(model, num_pages=scfg.max_batch * maxp + 1,
                               page_size=page, max_batch=scfg.max_batch,
                               max_pages_per_req=maxp, name=scfg.name)
        self.gid = self.kv.gid

    def admit(self, slot, prefill_cache, length):
        return self.kv.admit(slot, prefill_cache, length)

    def prepare_step(self, slot: int) -> bool:
        return self.kv.ensure_next_token(slot)

    def release(self, slot: int) -> None:
        self.kv.release(slot)

    def device_cache(self) -> Dict[str, jax.Array]:
        return self.kv.device_cache()

    def commit(self, new_cache: Dict[str, jax.Array]) -> None:
        self.kv.update_pools(new_cache)

    def step_bookkeeping(self, active: List[int]) -> None:
        self.kv.pos[active] += 1

    def pages_walked(self, active: List[int]) -> int:
        """Pages the step's attention reads over the active rows: those
        holding positions ``[0, pos]``, the new token's included."""
        page = self.kv.page_size
        return int(np.sum((self.kv.pos[active] + page) // page))

    def snapshot_slot(self, slot: int) -> Dict[str, Any]:
        return self.kv.snapshot_slot(slot)

    def restore_slot(self, slot: int, snap: Dict[str, Any]) -> bool:
        return self.kv.restore_slot(slot, snap)


# ----------------------------------------------------------------- engine
class Engine:
    def __init__(self, model: Model, params: Dict[str, jax.Array],
                 scfg: ServeConfig, extra_inputs: Optional[Dict[str, Any]] = None):
        self.model = model
        self.params = params
        self.scfg = scfg
        self.extra = extra_inputs or {}
        B = scfg.max_batch
        # paged families also prefill in static-shape buckets (valid_len);
        # the dense-slot families prefill at the prompt's exact length
        self.paged = model.supports_paged
        self.backend = (_PagedSlots(model, scfg) if self.paged
                        else _DenseSlots(model, scfg, self.extra))
        self.slots: List[Optional[_Request]] = [None] * B
        self._tokens = np.zeros((B, 1), np.int32)
        self._temp = np.zeros((B,), np.float32)
        self._topk = np.zeros((B,), np.int32)
        self._topp = np.ones((B,), np.float32)
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._ready: List[Tuple[_Request, Dict[str, jax.Array], int, int]] = []
        self._inflight_prefills = 0
        self._work_event = threading.Event()  # prefill completion wakeup
        self._lock = threading.Lock()
        self._running = False
        self._paused = False
        self._migrate_key: Optional[Tuple[int, int]] = None
        self._rid = 0
        self._step_count = 0
        self._key = jax.random.PRNGKey(scfg.seed)

        self._prefill = jax.jit(model.prefill, static_argnames=("cache_len",))
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))

        # Execution resources (HPX resource partitioner): executors are the
        # only path to scheduler pools.  Pool names resolve lazily at
        # submission, so engines survive runtime restarts.
        rt = current_runtime()
        if rt is not None:
            rt.add_pool(_PREFILL_POOL, _PREFILL_WORKERS)
        self._loop_exec = _executor.get_executor(
            _DECODE_POOL, fallback=_DECODE_POOL)  # → runtime default
        self._prefill_exec = _executor.get_executor(
            _PREFILL_POOL, priority=PRIORITY_HIGH, fallback=_DECODE_POOL)

        reg = _counters.default()
        n = scfg.name
        self.c_sub = reg.counter(f"/serve{{{n}}}/requests/submitted")
        self.c_done = reg.counter(f"/serve{{{n}}}/requests/completed")
        self.c_tok = reg.counter(f"/serve{{{n}}}/tokens/generated")
        self.c_walk = reg.counter(f"/serve{{{n}}}/decode/pages_walked")
        # percentile timers: p50/p95/p99 straight off the counter API —
        # "why is p99 bad" without needing a trace at all
        self.t_step = reg.timer(f"/serve{{{n}}}/step/duration",
                                percentiles=True)
        self.t_latency = reg.timer(f"/serve{{{n}}}/request/latency",
                                   percentiles=True)
        self.t_first = reg.timer(f"/serve{{{n}}}/request/first_token",
                                 percentiles=True)
        # live-migration accounting: migrated-out counts toward completed so
        # load() stays "requests this engine still has to do"
        self.c_mig_out = reg.counter(f"/serve{{{n}}}/requests/migrated_out")
        self.c_mig_in = reg.counter(f"/serve{{{n}}}/requests/migrated_in")
        # live tail-latency gauges: what the flight-recorder trigger polls
        # through the fleet sampler (seconds, from the timer histograms)
        reg.register_callable(f"/serve{{{n}}}/request/latency/p99",
                              lambda: self.t_latency.quantile(0.99))
        reg.register_callable(f"/serve{{{n}}}/request/first_token/p99",
                              lambda: self.t_first.quantile(0.99))

    # --------------------------------------------------------------- decode
    def _decode_fn(self, params, cache, token, key, temp, topk, topp):
        if self.paged:
            logits, new_cache = self.model.decode_paged(params, cache, token)
        else:
            logits, new_cache = self.model.decode(params, cache, token)
        with jax.named_scope("sample"):
            nxt = sample_logits(logits, key, temp, topk, topp)[:, None]
        return nxt, new_cache

    def decode_compile_count(self) -> int:
        """Distinct decode-step compilations (stays at 1 after warmup —
        admission churn must never change step shapes)."""
        return int(self._decode._cache_size())

    # ------------------------------------------------------------------ api
    def submit(self, prompt: List[int], max_new: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               stream: Optional[Channel] = None,
               meta: Optional[Dict[str, Any]] = None) -> Future:
        """One-sided request → Future[List[int]] of generated ids.

        ``stream``: optional Channel-alike — every generated token is
        ``set()`` the step it is sampled (first token before the request
        completes) and the channel closes when the request finishes.
        ``meta``: picklable routing info carried through live migration
        (the fleet relay's client locality + stream id).
        """
        if self._migrate_key is not None:
            # engine migrated away: answer with the stale-resolution signal
            # so the caller's apply_remote retry re-resolves to the new home
            from repro.net.locality import UnknownGid, current as _net_current
            net = _net_current()
            raise UnknownGid(self._migrate_key,
                             net.locality if net is not None else -1)
        with self._lock:
            self._rid += 1
            rid = self._rid
        tag = (meta or {}).get("req") or f"{self.scfg.name}/{rid}"
        req = _Request(rid, list(prompt),
                       self.scfg.max_new_tokens if max_new is None else max_new,
                       Promise(), sampling or GREEDY, stream,
                       submit_t=time.perf_counter(), meta=meta, tag=tag)
        self._queue.put(req)
        self.c_sub.increment()
        if _trace._enabled:  # request lifetime as one async span
            _trace.async_begin("request", rid, "serve",
                               prompt_len=len(req.prompt), req=tag,
                               slo=(meta or {}).get("slo"))
        self._ensure_running()
        return req.promise.future()

    def submit_stream(self, prompt: List[int], max_new: Optional[int] = None,
                      sampling: Optional[SamplingParams] = None
                      ) -> Tuple[Channel, Future]:
        ch: Channel = Channel()
        return ch, self.submit(prompt, max_new, sampling, stream=ch)

    def load(self) -> float:
        """In-flight requests (queued + prefilling + decoding) — the
        router's least-loaded dispatch metric."""
        return self.c_sub.get_value() - self.c_done.get_value()

    def occupancy(self) -> float:
        """Fraction of KV capacity in use (paged: block-pool pages; dense:
        occupied slots) — the admission-control signal the fleet gossips."""
        if self.paged:
            kv = self.backend.kv
            return kv.pages_in_use() / max(kv.num_pages - 1, 1)
        return sum(s is not None for s in self.slots) / self.scfg.max_batch

    def _ensure_running(self) -> None:
        with self._lock:
            if not self._running and not self._paused:
                self._running = True
                self._loop_exec.post(self._step)

    # ---------------------------------------------------------- migration
    def pause(self, timeout: float = 30.0) -> None:
        """Quiesce at a step boundary: stop the decode continuation chain
        and wait for in-flight prefills to land.  Queued / ready / active
        requests stay put; ``resume`` restarts the chain."""
        self._paused = True
        deadline = time.perf_counter() + timeout
        while True:
            with self._lock:
                if not self._running and self._inflight_prefills == 0:
                    return
            if time.perf_counter() > deadline:
                raise TimeoutError(f"engine {self.scfg.name}: pause timed out")
            time.sleep(0.002)

    def resume(self) -> None:
        self._paused = False
        self._ensure_running()

    def close_for_migration(self, key: Tuple[int, int]) -> None:
        """Point of no return for live migration: every subsequent
        ``submit`` raises :class:`UnknownGid` for ``key`` (this engine's
        GID), so remote callers' retry loop re-resolves through the AGAS
        root — which, once the destination adopts, names the new home."""
        self._migrate_key = tuple(key)

    def take_requests(self) -> Dict[str, Any]:
        """Drain every in-flight request into a picklable snapshot (the
        ship half of live migration; engine must be paused).

        Active slots travel with their paged KV (``snapshot_slot``) and
        resume mid-generation at the destination; queued / prefill-ready
        requests travel as prompts (prefill work is discarded — nothing
        was emitted for them yet, the destination re-prefills).  Requests
        must carry ``meta``: promises and channels are process-local, so
        only fleet-submitted traffic (whose relay re-attaches from meta)
        can be re-homed — anything else fails loudly rather than hang."""
        if not self._paused or self._running:
            raise RuntimeError("take_requests requires a paused engine")

        def _entry(req: _Request, kv=None, last_tok=None) -> Dict[str, Any]:
            # "client" marks relay meta specifically: router-tagged local
            # submits carry meta={"req","slo"} but no re-homeable sink
            if not req.meta or "client" not in req.meta:
                raise RuntimeError(
                    f"request {req.rid} has no relay meta; only "
                    f"fleet-submitted requests survive migration")
            e: Dict[str, Any] = {
                "prompt": req.prompt, "generated": req.generated,
                "max_new": req.max_new,
                "sampling": (req.sampling.temperature, req.sampling.top_k,
                             req.sampling.top_p),
                "meta": req.meta,
            }
            if kv is not None:
                e["kv"] = kv
                e["last_tok"] = last_tok
            return e

        snap: Dict[str, Any] = {"active": [], "queued": []}
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            snap["active"].append(_entry(req, self.backend.snapshot_slot(i),
                                         int(self._tokens[i, 0])))
            self.slots[i] = None
            self.backend.release(i)
            self._temp[i], self._topk[i], self._topp[i] = 0.0, 0, 1.0
            self.c_done.increment()
            self.c_mig_out.increment()
        with self._lock:
            ready, self._ready = self._ready, []
        queued = [r for r, _c, _l, _t in ready]
        while True:
            try:
                queued.append(self._queue.get_nowait())
            except queue.Empty:
                break
        for req in queued:
            snap["queued"].append(_entry(req))
            self.c_done.increment()
            self.c_mig_out.increment()
        return snap

    def _restored_request(self, e: Dict[str, Any]) -> _Request:
        with self._lock:
            self._rid += 1
            rid = self._rid
        t, k, p = e["sampling"]
        meta = dict(e["meta"])
        req = _Request(rid, list(e["prompt"]), int(e["max_new"]), Promise(),
                       SamplingParams(t, k, p), None,
                       generated=list(e["generated"]),
                       submit_t=time.perf_counter(), meta=meta,
                       tag=meta.get("req") or f"{self.scfg.name}/{rid}")
        if req.generated:  # first token happened at the source
            req.first_token_t = req.submit_t
        return req

    def restore_requests(self, snap: Dict[str, Any],
                         reattach: Optional[Any] = None) -> int:
        """Install a :meth:`take_requests` snapshot into this (paused)
        engine.  ``reattach(req)`` runs for every rebuilt request so the
        caller can wire a stream / completion hook from ``req.meta``
        before any token flows.  Returns the number of requests adopted."""
        if not self._paused or self._running:
            raise RuntimeError("restore_requests requires a paused engine")
        n = 0
        for e in snap["active"]:
            free = next((i for i, s in enumerate(self.slots) if s is None),
                        None)
            if free is None:
                raise RuntimeError("destination engine has no free slot for "
                                   "a migrated request")
            if not self.backend.restore_slot(free, e["kv"]):
                raise RuntimeError("destination page pool cannot hold a "
                                   "migrated request's KV")
            req = self._restored_request(e)
            if reattach is not None:
                reattach(req)
            self.slots[free] = req
            self._tokens[free, 0] = int(e["last_tok"])
            self._temp[free] = req.sampling.temperature
            self._topk[free] = req.sampling.top_k
            self._topp[free] = req.sampling.top_p
            self.c_sub.increment()
            self.c_mig_in.increment()
            n += 1
        for e in snap["queued"]:
            req = self._restored_request(e)
            if reattach is not None:
                reattach(req)
            self._queue.put(req)
            self.c_sub.increment()
            self.c_mig_in.increment()
            n += 1
        return n

    # ------------------------------------------------------------ admission
    def _bucket_for(self, n: int) -> int:
        """Smallest power-of-two bucket (≥ page_size) covering n, clamped to
        cache_len — static prefill shapes, no per-length recompiles."""
        b = max(self.scfg.page_size, 8)
        while b < n:
            b *= 2
        return min(b, self.scfg.cache_len)

    def _run_prefill(self, req: _Request):
        """Compute the request's KV cache + first token (any thread)."""
        n = len(req.prompt)
        bucket = self._bucket_for(n) if self.paged else n
        with _trace.span("prefill", "serve", rid=req.rid, req=req.tag,
                         prompt_len=n, bucket=bucket,
                         queued_ms=(time.perf_counter() - req.submit_t) * 1e3):
            payload = self._run_prefill_body(req, bucket)
        req.ready_t = time.perf_counter()
        return payload

    def _run_prefill_body(self, req: _Request, bucket: int):
        prompt = req.prompt
        if self.model.cfg.family == "vlm" and len(prompt) < self.model.cfg.n_patches:
            # patches occupy the first n_patches positions; a shorter prompt
            # would read logits from inside the patch region — fail loudly
            raise ValueError(f"vlm prompt needs ≥ {self.model.cfg.n_patches} "
                             f"tokens, got {len(prompt)}")
        pextra = {k: v for k, v in self.extra.items() if k != "enc_len"}
        if self.paged:
            assert len(prompt) <= bucket, (len(prompt), self.scfg.cache_len)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, : len(prompt)] = prompt
            pin = {"tokens": jnp.asarray(toks), **pextra}
            logits, cache1 = self._prefill(
                self.params, pin, cache_len=bucket,
                valid_len=jnp.asarray([len(prompt)], jnp.int32))
        else:
            pin = {"tokens": jnp.asarray(prompt, jnp.int32)[None, :], **pextra}
            logits, cache1 = self._prefill(self.params, pin,
                                           cache_len=self.scfg.cache_len)
        rng = np.random.default_rng((self.scfg.seed << 20) ^ req.rid)
        with _trace.span("prefill.wait", "serve"):
            tok0 = _sample_host(np.asarray(logits[0], np.float32),
                                req.sampling, rng)
        return req, cache1, len(prompt), tok0

    def _prefill_task(self, req: _Request) -> None:
        try:
            payload = self._run_prefill(req)
        except BaseException as e:  # noqa: BLE001 — fail the one request
            with self._lock:
                self._inflight_prefills -= 1
            if req.stream is not None:
                req.stream.close()
            self.c_done.increment()  # terminated: keep load() = in-flight
            if _trace._enabled:
                _trace.async_end("request", req.rid, "serve", failed=True,
                                 req=req.tag)
            req.promise.set_exception(e)
            self._work_event.set()
            return
        with self._lock:
            self._ready.append(payload)
            self._inflight_prefills -= 1
        self._work_event.set()
        self._ensure_running()

    def _pump_prefills(self) -> None:
        """Launch PRIORITY_HIGH prefill tasks for queued requests, keeping a
        bounded oversubscription so integration always has work ready."""
        while True:
            with self._lock:
                active = sum(s is not None for s in self.slots)
                budget = (self.scfg.max_batch - active
                          + _PREFILL_OVERSUB
                          - self._inflight_prefills - len(self._ready))
            if budget <= 0:
                return
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            self._launch_prefill(req)

    def _launch_prefill(self, req: _Request) -> None:
        with self._lock:
            self._inflight_prefills += 1
        self._prefill_exec.post(lambda: self._prefill_task(req))

    # ---------------------------------------------------------- integration
    def _emit(self, req: _Request, tok: int) -> None:
        req.generated.append(tok)
        self.c_tok.increment()
        if not req.first_token_t:
            req.first_token_t = time.perf_counter()
            self.t_first.add(req.first_token_t - req.submit_t)
        if req.stream is not None:
            req.stream.set(tok)

    def _finish(self, i: int) -> None:
        req = self.slots[i]
        self.slots[i] = None
        self.backend.release(i)
        self._temp[i], self._topk[i], self._topp[i] = 0.0, 0, 1.0
        self.c_done.increment()
        self.t_latency.add(time.perf_counter() - req.submit_t)
        if _trace._enabled:
            _trace.async_end("request", req.rid, "serve",
                             tokens=len(req.generated), req=req.tag)
        if req.stream is not None:
            req.stream.close()
        req.promise.set_value(req.generated)

    def _done_after(self, req: _Request, tok: int) -> bool:
        return (len(req.generated) >= req.max_new + 1
                or tok == self.scfg.eos_id)

    def _bind_slot(self, i: int, req: _Request, tok0: int) -> float:
        """Occupy slot ``i`` with an admitted request and emit its prefill
        token.  Returns how long the request's KV waited for the slot (s)."""
        waited = time.perf_counter() - req.ready_t
        self.slots[i] = req
        self._tokens[i, 0] = tok0
        self._temp[i] = req.sampling.temperature
        self._topk[i] = req.sampling.top_k
        self._topp[i] = req.sampling.top_p
        self._emit(req, tok0)
        if self._done_after(req, tok0):
            self._finish(i)
        return waited

    def _integrate_ready(self) -> List[float]:
        """Bind finished prefills to free slots; returns the KV-ready →
        slot-bound wait (s) of each request admitted."""
        waits: List[float] = []
        while True:
            free = next((i for i, s in enumerate(self.slots) if s is None), None)
            if free is None:
                return waits
            with self._lock:
                if not self._ready:
                    return waits
                payload = self._ready.pop(0)
            req, cache1, length, tok0 = payload
            if not self.backend.admit(free, cache1, length):
                if not any(s is not None for s in self.slots):
                    # nothing active will ever free pages → fail the request
                    # instead of wedging the head of the ready queue
                    if req.stream is not None:
                        req.stream.close()
                    req.promise.set_exception(RuntimeError(
                        f"request {req.rid}: {length} prompt tokens exceed "
                        f"page-pool capacity"))
                    self.c_done.increment()
                    continue
                if _trace._enabled:
                    # Waiting (W): the request has its KV ready but cannot
                    # enter a slot — page-pool contention, not queue wait
                    _trace.instant("admit_stall", "serve", req=req.tag,
                                   rid=req.rid)
                with self._lock:  # pool exhausted — retry after completions
                    self._ready.insert(0, payload)
                return waits
            waits.append(self._bind_slot(free, req, tok0))

    # ----------------------------------------------------------------- loop
    def _idle_or_stop(self) -> bool:
        """No active slots: block briefly on the queue (no hot-spin burning a
        worker) and decide whether the continuation chain ends."""
        with self._lock:
            waiting_on_prefill = bool(self._ready) or self._inflight_prefills > 0
        if waiting_on_prefill:  # integration work is coming — nap, don't spin
            self._work_event.wait(0.005)
            self._work_event.clear()
            return False
        try:
            req = self._queue.get(timeout=_IDLE_TIMEOUT)
        except queue.Empty:
            with self._lock:
                if (self._queue.empty() and not self._ready
                        and self._inflight_prefills == 0):
                    self._running = False  # chain ends; submit() restarts it
                    return True
            return False
        self._launch_prefill(req)
        return False

    def _step(self) -> None:
        """One link of the decode continuation chain."""
        if self._paused:  # quiesce at the step boundary; resume() restarts
            with self._lock:
                self._running = False
            return
        with _trace.span("admit", "serve") as span:
            self._pump_prefills()
            waits = self._integrate_ready()
            if waits:
                span.set(admitted=len(waits), ready_ms=max(waits) * 1e3)
            else:
                span.set(admitted=0)

        active = [i for i, s in enumerate(self.slots) if s is not None]
        for i in list(active):
            if not self.backend.prepare_step(i):  # can't grow: page capacity
                self._finish(i)
                active.remove(i)

        if not active:
            if self._idle_or_stop():
                return
            self._loop_exec.post(self._step)
            return

        step_args: Dict[str, Any] = {"step_num": self._step_count,
                                     "batch": len(active)}
        if self.paged:
            step_args["pages_walked"] = self.backend.pages_walked(active)
            self.c_walk.increment(step_args["pages_walked"])
        if _trace._enabled:
            # which requests this step advanced — the analyzer charges the
            # step's duration to every request decoding in it
            step_args["reqs"] = [self.slots[i].tag for i in active]
        with _trace.span("decode_step", "serve", **step_args), \
                self.t_step.time():
            with _trace.span("decode_step.dispatch", "serve"):
                key = jax.random.fold_in(self._key, self._step_count)
                nxt, new_cache = self._decode(
                    self.params, self.backend.device_cache(),
                    jnp.asarray(self._tokens), key,
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    jnp.asarray(self._topp))
            self.backend.commit(new_cache)
            with _trace.span("decode_step.wait", "serve"):
                toks = np.asarray(nxt[:, 0])
        self._step_count += 1
        with _trace.span("emit", "serve") as span:
            self.backend.step_bookkeeping(active)
            self._tokens[:, 0] = toks
            finished = 0
            for i in active:
                req = self.slots[i]
                tok = int(toks[i])
                self._emit(req, tok)
                if self._done_after(req, tok):
                    self._finish(i)
                    finished += 1
            span.set(finished=finished)
        self._loop_exec.post(self._step)  # continuation chain
