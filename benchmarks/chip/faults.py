"""Faults planted in the timed path, to show that ``correct`` catches them.

Not used by the benchmark's own runs: the tests drive a whole run with each
fault (``tests/bench/test_bench_faults.py``), and ``control.py --fault``
reads one at a cell's own size.  Each fault wraps a public method of the
system's model (``prefill``, ``decode_paged``) before the engine is built,
so the engine compiles the faulty step as its own:

- ``token_altered``: the prefill's first token is another one;
- ``state_unchanged``: the decode step returns its K/V state unchanged;
- ``half_batch``: half of the batch is left out of the decode step (those
  rows repeat their input token);
- ``prefill_cache_lost``: the prefill's K/V never reach the page pool.

One chip has no exchange between chips to leave out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def token_altered(model) -> None:
    prefill = model.prefill

    def altered(*args, **kw):
        logits, cache = prefill(*args, **kw)
        return jnp.roll(logits, 1, axis=-1), cache

    model.prefill = altered


def state_unchanged(model) -> None:
    decode = model.decode_paged

    def unchanged(params, cache, token):
        logits, _ = decode(params, cache, token)
        return logits, cache

    model.decode_paged = unchanged


def half_batch(model) -> None:
    decode = model.decode_paged

    def half(params, cache, token):
        logits, new = decode(params, cache, token)
        rows = token.shape[0]
        keep = (jnp.arange(rows) < rows // 2)[:, None]
        repeat = jax.nn.one_hot(token[:, 0], logits.shape[-1], dtype=logits.dtype)
        return jnp.where(keep, logits, repeat * 1e4), new

    model.decode_paged = half


def prefill_cache_lost(model) -> None:
    prefill = model.prefill

    def lost(*args, **kw):
        logits, cache = prefill(*args, **kw)
        return logits, jax.tree_util.tree_map(jnp.zeros_like, cache)

    model.prefill = lost


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged, half_batch,
                                  prefill_cache_lost)}
