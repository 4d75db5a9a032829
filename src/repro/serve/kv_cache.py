"""Block-pool paged KV cache (the serving stack's cache layer).

The seed engine allocated a dense ``(L, max_batch, cache_len, KV, Dh)``
cache — memory ∝ ``max_batch × cache_len`` whether slots are full or empty.
This module replaces it with a vLLM-style block pool: KV lives in
``num_pages`` fixed-size pages shared by all requests and all layers (page
``p`` holds a request's tokens in *every* layer array), a free list hands
pages out on demand, and each batch slot owns a page list mirrored into a
``(max_batch, max_pages_per_req)`` page table that the paged decode kernel
walks (``kernels/decode_attention.py::paged_attention_fwd``).
Memory therefore scales with *live tokens*.

Page 0 is reserved as a scratch page: idle slots' page tables point at it,
so the batched decode step can write their (discarded) K/V somewhere
harmless without per-slot branching.

Ownership split with the engine: this class owns *allocation* (host-side
free list, page-table / pos mirrors, prefill scatter) and the device page
pools; the engine drives the jitted decode step, passing
:meth:`device_cache` in and storing the donated-out pools back via
:meth:`update_pools`.  The pool pytree is AGAS-registered, so elastic
rebalancing moves it like any other global object (DESIGN.md §5).

Performance counters::

    /serve{<name>}/pages/in_use        gauge
    /serve{<name>}/pages/capacity      gauge
    /serve{<name>}/pages/allocated     cumulative
    /serve{<name>}/pages/freed         cumulative
    /serve{<name>}/pages/alloc_failures cumulative
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agas as _agas
from repro.core import counters as _counters

_POOL_KEYS = ("k", "v", "k0", "v0", "ckv", "ckv0")  # K/V, or the MLA latent


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(pool: jax.Array, src: jax.Array,
                   page_ids: jax.Array) -> jax.Array:
    """pool (L,P,KV,page,Dh) ← src (L,npg,KV,page,Dh) at pages ``page_ids``."""
    return pool.at[:, page_ids].set(src.astype(pool.dtype))


class PagedKVCache:
    """Fixed-page block pool + free list + per-slot page tables."""

    def __init__(self, model, *, num_pages: int, page_size: int,
                 max_batch: int, max_pages_per_req: int,
                 name: str = "engine#0"):
        assert num_pages >= 2, "need at least the scratch page plus one"
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_batch = max_batch
        self.max_pages_per_req = max_pages_per_req
        specs = model.paged_cache_specs(num_pages, page_size, max_batch,
                                        max_pages_per_req)
        self.pools: Dict[str, jax.Array] = {
            k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()
            if k in _POOL_KEYS
        }
        # host-authoritative mirrors (admission mutates them between steps)
        self.page_table = np.zeros((max_batch, max_pages_per_req), np.int32)
        self.pos = np.zeros((max_batch,), np.int32)
        # LIFO free list; page 0 reserved as the idle-slot scratch page
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._owned: Dict[int, List[int]] = {i: [] for i in range(max_batch)}

        reg = _counters.default()
        self.g_in_use = reg.gauge(f"/serve{{{name}}}/pages/in_use")
        self.g_capacity = reg.gauge(f"/serve{{{name}}}/pages/capacity")
        self.g_capacity.set(float(num_pages - 1))
        self.c_alloc = reg.counter(f"/serve{{{name}}}/pages/allocated")
        self.c_freed = reg.counter(f"/serve{{{name}}}/pages/freed")
        self.c_fail = reg.counter(f"/serve{{{name}}}/pages/alloc_failures")
        self.gid = _agas.default().register(self.pools, name=None,
                                            placement="host-engine")

    # ------------------------------------------------------------ free list
    def free_pages(self) -> int:
        return len(self._free)

    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def _take(self, n: int) -> Optional[List[int]]:
        if len(self._free) < n:
            self.c_fail.increment()
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.c_alloc.increment(n)
        self.g_in_use.set(float(self.pages_in_use()))
        return pages

    # ------------------------------------------------------------ slot api
    def admit(self, slot: int, prefill_cache: Dict[str, jax.Array],
              length: int) -> bool:
        """Bind ``slot`` to a freshly prefilled request: allocate pages for
        its ``length`` valid tokens and scatter the (possibly right-padded)
        prefill rows of every pool (K and V, or the latent) into them.
        Returns False if the pool is exhausted (caller retries after the
        next completion frees pages)."""
        assert not self._owned[slot], f"slot {slot} still owns pages"
        npg = -(-length // self.page_size)  # ceil
        if npg > self.max_pages_per_req:
            return False
        pages = self._take(npg)
        if pages is None:
            return False
        ids = jnp.asarray(pages, jnp.int32)
        for key in self.pools:
            src = prefill_cache[key][:, 0]  # (L, S_bucket, KV, Dh)
            L, S, KV, Dh = src.shape
            pad = npg * self.page_size - S
            if pad > 0:
                src = jnp.pad(src, ((0, 0), (0, pad), (0, 0), (0, 0)))
            src = src[:, : npg * self.page_size]
            src = src.reshape(L, npg, self.page_size, KV, Dh
                              ).transpose(0, 1, 3, 2, 4)
            self.pools[key] = _scatter_pages(self.pools[key], src, ids)
        self._owned[slot] = pages
        self.page_table[slot, :] = 0
        self.page_table[slot, :npg] = pages
        self.pos[slot] = length
        return True

    def ensure_next_token(self, slot: int) -> bool:
        """Make sure the page holding token index ``pos[slot]`` exists.
        Returns False when the slot can no longer grow (page-table capacity
        or pool exhaustion) — the engine finishes the request."""
        idx = int(self.pos[slot]) // self.page_size
        owned = self._owned[slot]
        if idx < len(owned):
            return True
        if idx >= self.max_pages_per_req:
            return False
        pages = self._take(1)
        if pages is None:
            return False
        owned.append(pages[0])
        self.page_table[slot, idx] = pages[0]
        return True

    def release(self, slot: int) -> None:
        """Return the slot's pages to the free list (admission churn path)."""
        pages, self._owned[slot] = self._owned[slot], []
        if pages:
            self._free.extend(reversed(pages))
            self.c_freed.increment(len(pages))
            self.g_in_use.set(float(self.pages_in_use()))
        self.page_table[slot, :] = 0
        self.pos[slot] = 0

    # ------------------------------------------------------- migration i/o
    def snapshot_slot(self, slot: int) -> Dict[str, Any]:
        """Host copy of one slot's live KV state: the pages it owns (in
        page-table order) gathered out of every pool, plus its position.
        This is the unit live engine migration ships — pages for *live
        tokens only*, never the whole pool."""
        pages = self._owned[slot]
        ids = np.asarray(pages, np.int32)
        return {
            "pos": int(self.pos[slot]),
            "pages": {k: np.asarray(jax.device_get(pool[:, ids]))
                      for k, pool in self.pools.items()} if pages else {},
            "n_pages": len(pages),
        }

    def restore_slot(self, slot: int, snap: Dict[str, Any]) -> bool:
        """Re-home a snapshotted slot into *this* pool: allocate fresh pages
        (the page ids are locality-local — only the contents travel) and
        scatter the shipped KV into them.  Returns False when this pool
        cannot hold the slot (caller must not have dropped the source
        yet)."""
        assert not self._owned[slot], f"slot {slot} still owns pages"
        npg = int(snap["n_pages"])
        if npg == 0:
            self.pos[slot] = snap["pos"]
            return True
        if npg > self.max_pages_per_req:
            return False
        pages = self._take(npg)
        if pages is None:
            return False
        ids = jnp.asarray(pages, jnp.int32)
        for key in self.pools:
            self.pools[key] = _scatter_pages(self.pools[key],
                                             jnp.asarray(snap["pages"][key]),
                                             ids)
        self._owned[slot] = pages
        self.page_table[slot, :] = 0
        self.page_table[slot, :npg] = pages
        self.pos[slot] = snap["pos"]
        return True

    # ------------------------------------------------------------- step i/o
    def device_cache(self) -> Dict[str, jax.Array]:
        """The pytree the jitted paged decode step consumes (pool arrays are
        donated out by the step; page table / pos re-upload from the
        host-authoritative mirrors each step — a few hundred bytes)."""
        cache = dict(self.pools)
        cache["page_table"] = jnp.asarray(self.page_table)
        cache["pos"] = jnp.asarray(self.pos)
        return cache

    def update_pools(self, new_cache: Dict[str, jax.Array]) -> None:
        # self.pools is the AGAS-registered object; in-place update keeps the
        # global view current without a rebind (which would count a migration)
        for key in self.pools:
            self.pools[key] = new_cache[key]
