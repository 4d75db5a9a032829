"""Operations and bytes the model needs, from its sizes and live lengths.

These are what the algorithm requires, not what an implementation happens
to read: a decode step needs the weights once, the live K/V of each active
row and the new K/V it writes; a prefill needs its valid (unpadded) prompt
tokens.  A multiply-add counts as two operations.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

from model_spec import ModelSpec

BF16 = 2  # bytes per served weight and per K/V element


def layer_params(m: ModelSpec) -> int:
    D, F = m.hidden, m.ffn
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    n = D * q + 2 * D * kv + q * D + (3 if m.gated else 2) * D * F
    n += 2 * D  # two norm scales
    if m.qkv_bias:
        n += q + 2 * kv
    return n


def layer_matmul_params(m: ModelSpec) -> int:
    D, F = m.hidden, m.ffn
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return D * q + 2 * D * kv + q * D + (3 if m.gated else 2) * D * F


def param_count(m: ModelSpec) -> int:
    """Parameters as served: tied embedding (padded rows), final norm, layers."""
    head = 0 if m.tied else m.hidden * m.padded_vocab
    return m.padded_vocab * m.hidden + head + m.hidden + m.layers * layer_params(m)


def kv_bytes_per_token(m: ModelSpec) -> int:
    return m.layers * 2 * m.kv_heads * m.head_dim * BF16


def _attn_flops(m: ModelSpec, q_len: int, k_len: float) -> float:
    """QK^T and PV for ``q_len`` queries over ``k_len`` keys, all layers."""
    return 4.0 * m.layers * m.heads * m.head_dim * q_len * k_len


def decode_step(m: ModelSpec, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step over active rows whose K/V hold ``contexts`` tokens
    before the step (the new token attends to ``context + 1`` keys)."""
    rows = len(contexts)
    flops = rows * 2.0 * (m.layers * layer_matmul_params(m)
                          + m.hidden * m.padded_vocab)
    flops += sum(_attn_flops(m, 1, c + 1) for c in contexts)
    weights = param_count(m) * BF16
    kv = sum(contexts) * kv_bytes_per_token(m) + rows * kv_bytes_per_token(m)
    return {"flops": flops, "bytes": float(weights + kv)}


def prefill(m: ModelSpec, prompt_len: int) -> float:
    """Useful operations of one prompt: every valid token through every
    layer, causal attention, and the logits of the last position."""
    n = prompt_len
    flops = 2.0 * n * m.layers * layer_matmul_params(m)
    flops += _attn_flops(m, 1, 1) * n * (n + 1) / 2
    return flops + 2.0 * m.hidden * m.padded_vocab


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
