"""The dense decoder, written once: each layer is attention, then an MLP.

A family module (``families/<model_type>.py``, found by ``manifest.family``)
provides what the harness reads through it:

- ``spec(name, conf)``: the ``ModelSpec`` (or an extension of it) read from
  the configuration file's published keys;
- ``layout(m)``: the leaves the system holds (``weights.Layout``): global
  leaves, among them ``embed``, the input embedding, and stacks of layers;
- ``layer(m, prefix, dt, w, x, mm)``: one layer of the stack ``prefix`` in
  float32 (the reference) or in the control's precision, over a whole
  sequence ``x`` (S, D), with every weight matrix multiplication through
  ``mm(a, b)``;
- ``final(m, glob, x)`` and ``logits_table(m, glob)``: the final norm, and
  the (rows >= vocab, D) table of the logits, tied or not;
- ``kv_bytes_per_token(m)``, ``attn_flops(m, q_len, k_len)``,
  ``decode_step(m, contexts)`` and ``prefill(m, prompt_len)``: what the
  model needs, in operations and bytes (``flops.py``);
- ``check_system(cfg, m, cache_len)``: refuses a system model that is not
  the file's.

Qwen2 and StarCoder2 take all but ``spec`` from here (``__all__``) and
name only how their configurations spell the norm, the MLP and the biases.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

import flops
from model_spec import ModelSpec
from weights import Layout, Stack

__all__ = ["layout", "layer", "final", "logits_table", "kv_bytes_per_token",
           "attn_flops", "decode_step", "prefill", "check_system"]

Q_CHUNK = 512  # queries per attention block


def read_spec(name: str, conf: dict, *, norm: str, eps_key: str, gated: bool,
              act: str, qkv_bias: bool) -> ModelSpec:
    """The published keys every dense decoder shares; the family gives how
    its config spells the rest."""
    heads = int(conf["num_attention_heads"])
    hidden = int(conf["hidden_size"])
    window = 0
    if conf.get("sliding_window") and conf.get("use_sliding_window", True):
        window = int(conf["sliding_window"])
    return ModelSpec(
        name=name, model_type=conf["model_type"],
        layers=int(conf["num_hidden_layers"]), hidden=hidden, heads=heads,
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf.get("head_dim") or hidden // heads),
        ffn=int(conf["intermediate_size"]), vocab=int(conf["vocab_size"]),
        norm=norm, eps=float(conf[eps_key]), gated=gated, act=act,
        qkv_bias=qkv_bias, rope_theta=float(conf["rope_theta"]),
        tied=bool(conf.get("tie_word_embeddings", False)), window=window)


# ------------------------------------------------------------------ layout
def attention_leaves(m: ModelSpec) -> Dict[str, tuple]:
    D = m.hidden
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    leaves = {"ln1": ((D,), "norm"), "wq": ((D, q), "matrix"),
              "wk": ((D, kv), "matrix"), "wv": ((D, kv), "matrix"),
              "wo": ((q, D), "matrix")}
    if m.qkv_bias:
        leaves.update(bq=((q,), "bias"), bk=((kv,), "bias"),
                      bv=((kv,), "bias"))
    return leaves


def mlp_leaves(m: ModelSpec) -> Dict[str, tuple]:
    D, F = m.hidden, m.ffn
    leaves = {"ln2": ((D,), "norm"), "w_in": ((D, F), "matrix"),
              "w_out": ((F, D), "matrix")}
    if m.gated:
        leaves["w_gate"] = ((D, F), "matrix")
    return leaves


def global_leaves(m: ModelSpec) -> Dict[str, tuple]:
    """Embedding, final norm and, where untied, the output head."""
    glob = {"embed": ("tok_embed", (m.padded_vocab, m.hidden), "embed"),
            "final_norm": ("final_ln", (m.hidden,), "norm")}
    if not m.tied:
        glob["lm_head"] = ("lm_head", (m.hidden, m.padded_vocab), "matrix")
    return glob


def layout(m: ModelSpec) -> Layout:
    return Layout(global_leaves(m), (Stack("blk/", 0, m.layers, {
        **attention_leaves(m), **mlp_leaves(m)}),))


# --------------------------------------------------------------- reference
def norm(m: ModelSpec, x, w):
    xf = x.astype(jnp.float32)
    if m.norm == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + m.eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(
            jnp.mean((xf - mu) ** 2, -1, keepdims=True) + m.eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(m: ModelSpec, x, pos):
    """x: (S, h, Dh); rotate-half with ``rope_theta``."""
    half = x.shape[-1] // 2
    freqs = m.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


def attention(m: ModelSpec, dt, w, x, mm):
    """x + grouped-query causal self-attention of norm(x) (banded where the
    model has a sliding window), over a whole sequence x: (S, D)."""
    S = x.shape[0]
    H, KV, Dh = m.heads, m.kv_heads, m.head_dim
    G = H // KV
    pos = jnp.arange(S)
    h = norm(m, x, w["ln1"])
    q, k, v = mm(h, w["wq"]), mm(h, w["wk"]), mm(h, w["wv"])
    if m.qkv_bias:
        q = q + w["bq"].astype(dt)
        k = k + w["bk"].astype(dt)
        v = v + w["bv"].astype(dt)
    q = rope(m, q.reshape(S, H, Dh), pos).reshape(S, KV, G, Dh)
    k = rope(m, k.reshape(S, KV, Dh), pos)
    v = v.reshape(S, KV, Dh)
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k,
                       preferred_element_type=jnp.float32) / math.sqrt(Dh)
        qpos = (c0 + jnp.arange(qc.shape[0]))[:, None]
        ok = pos[None, :] <= qpos
        if m.window:
            ok = ok & (pos[None, :] > qpos - m.window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p.astype(dt), v,
                               preferred_element_type=jnp.float32).astype(dt))
    o = jnp.concatenate(outs, 0).reshape(S, H * Dh)
    return x + mm(o, w["wo"])


def mlp(m: ModelSpec, dt, w, h, mm):
    """SwiGLU or a plain GELU (tanh) MLP of h."""
    if m.gated:
        a = jax.nn.silu(mm(h, w["w_gate"]).astype(jnp.float32))
        u = (a * mm(h, w["w_in"]).astype(jnp.float32)).astype(dt)
    else:
        u = jax.nn.gelu(mm(h, w["w_in"]).astype(jnp.float32),
                        approximate=True).astype(dt)
    return mm(u, w["w_out"])


def layer(m: ModelSpec, prefix: str, dt, w: Dict[str, jax.Array], x, mm):
    x = attention(m, dt, w, x, mm)
    return x + mlp(m, dt, w, norm(m, x, w["ln2"]), mm)


def final(m: ModelSpec, glob, x):
    return norm(m, x, glob["final_norm"])


def logits_table(m: ModelSpec, glob):
    return glob["embed"] if m.tied else glob["lm_head"].T


# ------------------------------------------------------------------ counts
def matmul_params(m: ModelSpec) -> int:
    """Weights of one layer's matrix multiplications."""
    D, F = m.hidden, m.ffn
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return D * q + 2 * D * kv + q * D + (3 if m.gated else 2) * D * F


def kv_bytes_per_token(m: ModelSpec) -> int:
    return m.layers * 2 * m.kv_heads * m.head_dim * flops.BF16


def attn_flops(m: ModelSpec, q_len: int, k_len: float) -> float:
    """QK^T and PV for ``q_len`` queries over ``k_len`` keys, all layers."""
    return 4.0 * m.layers * m.heads * m.head_dim * q_len * k_len


def decode_step(m: ModelSpec, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step over active rows whose K/V hold ``contexts`` tokens
    before the step (the new token attends to ``context + 1`` keys): every
    weight read once, the live K/V of each row, and the new K/V."""
    rows = len(contexts)
    ops = rows * 2.0 * (m.layers * matmul_params(m)
                        + m.hidden * m.padded_vocab)
    ops += sum(attn_flops(m, 1, c + 1) for c in contexts)
    weights = flops.param_count(m) * flops.BF16
    kv = sum(contexts) * kv_bytes_per_token(m) + rows * kv_bytes_per_token(m)
    return {"flops": ops, "bytes": float(weights + kv)}


def prefill(m: ModelSpec, prompt_len: int) -> float:
    """Useful operations of one prompt: every valid token through every
    layer, causal attention, and the logits of the last position."""
    n = prompt_len
    ops = 2.0 * n * m.layers * matmul_params(m)
    ops += attn_flops(m, 1, 1) * n * (n + 1) / 2
    return ops + 2.0 * m.hidden * m.padded_vocab


# ------------------------------------------------------------ system check
def check_system(cfg, m: ModelSpec, cache_len: int) -> None:
    """The system's model must be the configuration file's."""
    have = dict(layers=cfg.num_layers, hidden=cfg.d_model,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, ffn=cfg.d_ff, vocab=cfg.vocab_size,
                norm={"rmsnorm": "rms", "layernorm": "layer"}[cfg.norm],
                gated=cfg.glu, act={"silu": "silu", "gelu": "gelu_tanh"}[cfg.act],
                qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
                tied=cfg.tie_embeddings)
    want = {k: getattr(m, k) for k in have}
    if have != want or cfg.family != "dense" or cfg.window:
        raise ValueError(f"{m.name}: the system's model differs from the "
                         f"configuration file: {have} != {want}")
    if m.window and m.window < cache_len:
        raise ValueError(f"{m.name}: a {m.window}-token window masks keys "
                         f"at cache_len {cache_len}; the system has none")
