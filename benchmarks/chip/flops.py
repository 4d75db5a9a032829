"""Operations and bytes the model needs, from its sizes and live lengths.

These are what the algorithm requires, not what an implementation happens
to read: a decode step needs the weights it computes with, the live K/V of
each active row and the new K/V it writes; a prefill needs its valid
(unpadded) prompt tokens.  A multiply-add counts as two operations.  The
model's own arithmetic is its family module's (``families/``); the
parameters as served are counted from its layout.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

import manifest
import weights
from model_spec import ModelSpec

BF16 = 2  # bytes per served weight and per K/V element


def _family(m: ModelSpec):
    return manifest.family(m.model_type)


def param_count(m: ModelSpec) -> int:
    """Parameters as served: every leaf of the family's layout, padded
    vocabulary rows included."""
    return weights.param_count(_family(m).layout(m))


def kv_bytes_per_token(m: ModelSpec) -> int:
    return _family(m).kv_bytes_per_token(m)


def _attn_flops(m: ModelSpec, q_len: int, k_len: float) -> float:
    """QK^T and PV for ``q_len`` queries over ``k_len`` keys, all layers."""
    return _family(m).attn_flops(m, q_len, k_len)


def decode_step(m: ModelSpec, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step over active rows whose K/V hold ``contexts`` tokens
    before the step (the new token attends to ``context + 1`` keys):
    ``{"flops": ..., "bytes": ...}``."""
    return _family(m).decode_step(m, contexts)


def prefill(m: ModelSpec, prompt_len: int) -> float:
    """Useful operations of one prompt of ``prompt_len`` valid tokens."""
    return _family(m).prefill(m, prompt_len)


def least_time(flops: float, nbytes: float, peaks: dict) -> Dict[str, float]:
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def decode_least_time(m: ModelSpec, steps: Iterable[Sequence[int]],
                      peaks: dict) -> Dict[str, float]:
    """Summed least time of several decode steps, and how many of them
    were bound by memory bandwidth."""
    total, by_mem, n = 0.0, 0, 0
    for contexts in steps:
        c = decode_step(m, contexts)
        t = least_time(c["flops"], c["bytes"], peaks)
        total += t["seconds"]
        by_mem += t["bound"] == "memory"
        n += 1
    return {"seconds": total, "steps": n, "memory_bound_steps": by_mem}
