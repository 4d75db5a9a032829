"""DeepSeek-V2 (``model_type`` "deepseek_v2"): multi-head latent attention
with YaRN rotary scaling, a leading dense layer, then MoE layers of routed
experts behind a softmax router and shared experts.

What the configuration file holds beside the published keys: the routed
experts held here (``n_routed_experts``, listed in ``reduced``) and, under
``expert_parallel``, the published count the router keeps, how many chips
share each MoE layer and which share this chip holds.  The layer's result is
that share's: the held experts' weighted outputs plus the shared experts;
assignments to the other chips' experts add nothing, in the system and here
alike.

The reference follows ``modeling_deepseek.py`` (``DeepseekV2Attention``,
``DeepseekV2YarnRotaryEmbedding``, ``MoEGate``, ``DeepseekV2MoE``) in the
plain, non-absorbed form: per head ``k_nope ‖ v = RMSNorm(c_kv) W_kv_b``,
one ``k_pe`` shared by the heads, RoPE on the de-interleaved rope dims.
The norm, the dense MLP, ``final`` and ``logits_table`` are the dense
decoder's.
"""

import math
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops
from dense_decoder import Q_CHUNK, final, global_leaves, logits_table, mlp, norm
from model_spec import ModelSpec
from weights import Layout, Stack

__all__ = ["spec", "layout", "layer", "final", "logits_table",
           "kv_bytes_per_token", "attn_flops", "decode_step", "prefill",
           "expert_step", "check_system"]


# no ``from __future__ import annotations`` here: the manifest loads this
# module without registering it, and dataclass resolves string annotations
# through ``sys.modules``
@dataclass(frozen=True)
class DeepseekV2Spec(ModelSpec):
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    first_dense: int       # leading layers with a dense MLP
    dense_ffn: int         # their width (``intermediate_size``)
    experts: int           # routed experts the router scores (published)
    held: int              # routed experts held here (``n_routed_experts``)
    shard: int             # which share of ``experts // held`` is held
    per_token: int         # ``num_experts_per_tok``
    shared: int            # ``n_shared_experts``, each of width ``ffn``
    norm_topk: bool
    routed_scale: float
    # rope_scaling: factor, original positions, beta_fast, beta_slow,
    # mscale, mscale_all_dim
    yarn: Tuple[float, int, float, float, float, float]


def spec(name: str, conf: dict) -> DeepseekV2Spec:
    ys = conf["rope_scaling"]
    ep = conf["expert_parallel"]
    held, experts = int(conf["n_routed_experts"]), int(ep["router_experts"])
    if ys.get("type") != "yarn" or conf["q_lora_rank"] is not None:
        raise ValueError(f"{name}: only YaRN rope scaling and no q LoRA "
                         f"are written here")
    if (conf["scoring_func"], conf["topk_method"]) != ("softmax", "greedy"):
        raise ValueError(f"{name}: softmax scoring and greedy top-k only")
    if experts != held * int(ep["chips"]) or not 0 <= ep["this_chip"] < ep["chips"]:
        raise ValueError(f"{name}: {held} experts on each of {ep['chips']} "
                         f"chips are not the router's {experts}")
    if conf["moe_layer_freq"] != 1 or conf["attention_bias"]:
        raise ValueError(f"{name}: an MoE layer after every dense one and "
                         f"no attention bias only")
    heads = int(conf["num_attention_heads"])
    return DeepseekV2Spec(
        name=name, model_type=conf["model_type"],
        layers=int(conf["num_hidden_layers"]), hidden=int(conf["hidden_size"]),
        heads=heads, kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["qk_nope_head_dim"]) + int(conf["qk_rope_head_dim"]),
        ffn=int(conf["moe_intermediate_size"]), vocab=int(conf["vocab_size"]),
        norm="rms", eps=float(conf["rms_norm_eps"]), gated=True, act="silu",
        qkv_bias=False, rope_theta=float(conf["rope_theta"]),
        tied=bool(conf["tie_word_embeddings"]), window=0,
        kv_lora_rank=int(conf["kv_lora_rank"]),
        qk_nope=int(conf["qk_nope_head_dim"]),
        qk_rope=int(conf["qk_rope_head_dim"]), v_head=int(conf["v_head_dim"]),
        first_dense=int(conf["first_k_dense_replace"]),
        dense_ffn=int(conf["intermediate_size"]), experts=experts, held=held,
        shard=int(ep["this_chip"]), per_token=int(conf["num_experts_per_tok"]),
        shared=int(conf["n_shared_experts"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        yarn=(float(ys["factor"]), int(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys["mscale"]), float(ys["mscale_all_dim"])))


# ------------------------------------------------------------------ layout
def attention_leaves(m: DeepseekV2Spec) -> Dict[str, tuple]:
    D, H, R = m.hidden, m.heads, m.kv_lora_rank
    return {"ln1": ((D,), "norm"),
            "wq": ((D, H * (m.qk_nope + m.qk_rope)), "matrix"),
            "wkv_a": ((D, R + m.qk_rope), "matrix"),
            "kv_norm": ((R,), "norm"),
            "wkv_b": ((R, H * (m.qk_nope + m.v_head)), "matrix"),
            "wo": ((H * m.v_head, D), "matrix")}


def dense_leaves(m: DeepseekV2Spec) -> Dict[str, tuple]:
    D, F = m.hidden, m.dense_ffn
    return {"ln2": ((D,), "norm"), "w_in": ((D, F), "matrix"),
            "w_gate": ((D, F), "matrix"), "w_out": ((F, D), "matrix")}


def moe_leaves(m: DeepseekV2Spec) -> Dict[str, tuple]:
    """The router over all experts, the held experts as rank-3 matrices,
    the shared experts as one MLP of ``shared`` times the expert width."""
    D, F, E = m.hidden, m.ffn, m.held
    Fs = m.shared * F
    return {"ln2": ((D,), "norm"), "moe/router": ((D, m.experts), "matrix"),
            "moe/w_in": ((E, D, F), "matrix"),
            "moe/w_gate": ((E, D, F), "matrix"),
            "moe/w_out": ((E, F, D), "matrix"),
            "moe/shared_w_in": ((D, Fs), "matrix"),
            "moe/shared_w_gate": ((D, Fs), "matrix"),
            "moe/shared_w_out": ((Fs, D), "matrix")}


def layout(m: DeepseekV2Spec) -> Layout:
    fd = m.first_dense
    return Layout(global_leaves(m), (
        Stack("d0/", 0, fd, {**attention_leaves(m), **dense_leaves(m)}),
        Stack("blk/", fd, m.layers - fd, {**attention_leaves(m),
                                          **moe_leaves(m)})))


# --------------------------------------------------------------- reference
def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(m: DeepseekV2Spec) -> np.ndarray:
    """YaRN's inverse frequencies of the ``qk_rope`` dims, float64."""
    factor, orig, fast, slow, _, _ = m.yarn
    dim = m.qk_rope
    base = m.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(m.rope_theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def rope(m: DeepseekV2Spec, x, pos):
    """x: (S, h, Dr): de-interleave the dims, then rotate half, cos/sin
    scaled by ``mscale / mscale_all_dim``."""
    factor, _, _, _, ms, ms_all = m.yarn
    scale = _yarn_mscale(factor, ms) / _yarn_mscale(factor, ms_all)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(m), jnp.float32)
    c = (jnp.cos(ang) * scale)[:, None, :]
    s = (jnp.sin(ang) * scale)[:, None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    xf = jnp.swapaxes(xf, -1, -2).reshape(x.shape)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


def softmax_scale(m: DeepseekV2Spec) -> float:
    factor, _, _, _, _, ms_all = m.yarn
    s = (m.qk_nope + m.qk_rope) ** -0.5
    if ms_all:
        s *= _yarn_mscale(factor, ms_all) ** 2
    return s


def attention(m: DeepseekV2Spec, dt, w, x, mm):
    """x + causal multi-head latent attention of norm(x), non-absorbed."""
    S = x.shape[0]
    H, Dn, Dr, Dv, R = m.heads, m.qk_nope, m.qk_rope, m.v_head, m.kv_lora_rank
    pos = jnp.arange(S)
    h = norm(m, x, w["ln1"])
    q = mm(h, w["wq"]).reshape(S, H, Dn + Dr)
    a = mm(h, w["wkv_a"])
    c = norm(m, a[:, :R], w["kv_norm"])  # kv_a_layernorm: eps 1e-6 too
    k_pe = rope(m, a[:, None, R:], pos)
    kv = mm(c, w["wkv_b"]).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(k_pe, (S, H, Dr))], -1)
    v = kv[..., Dn:]
    q = jnp.concatenate([q[..., :Dn], rope(m, q[..., Dn:], pos)], -1)
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        s = jnp.einsum("qhd,thd->hqt", qc, k,
                       preferred_element_type=jnp.float32) * softmax_scale(m)
        ok = pos[None, :] <= (c0 + jnp.arange(qc.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p.astype(dt), v,
                               preferred_element_type=jnp.float32).astype(dt))
    o = jnp.concatenate(outs, 0).reshape(S, H * Dv)
    return x + mm(o, w["wo"])


def moe(m: DeepseekV2Spec, dt, w, h, mm):
    """The held experts' part of the routed mixture, plus the shared
    experts: softmax over all ``experts`` scores, greedy top-k, the top-k
    weights renormalised only where ``norm_topk`` says, times
    ``routed_scale``; each held expert over every token, weighted by its
    routing weight (zero where the token does not route to it)."""
    probs = jax.nn.softmax(mm(h, w["moe/router"]).astype(jnp.float32), -1)
    wts, idx = jax.lax.top_k(probs, m.per_token)
    if m.norm_topk:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    wts = wts * m.routed_scale
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(m.held):
        g = jnp.sum(jnp.where(idx == m.shard * m.held + e, wts, 0.0), -1)
        ex = {"w_in": w["moe/w_in"][e], "w_gate": w["moe/w_gate"][e],
              "w_out": w["moe/w_out"][e]}
        out = out + g[:, None] * mlp(m, dt, ex, h, mm).astype(jnp.float32)
    shared = {"w_in": w["moe/shared_w_in"], "w_gate": w["moe/shared_w_gate"],
              "w_out": w["moe/shared_w_out"]}
    return (out + mlp(m, dt, shared, h, mm).astype(jnp.float32)).astype(dt)


def layer(m: DeepseekV2Spec, prefix: str, dt, w: Dict[str, jax.Array], x, mm):
    x = attention(m, dt, w, x, mm)
    h = norm(m, x, w["ln2"])
    return x + (mlp(m, dt, w, h, mm) if prefix == "d0/" else moe(m, dt, w, h, mm))


# ------------------------------------------------------------------ counts
def _attn_params(m: DeepseekV2Spec) -> int:
    return sum(int(np.prod(s)) for s, kind in attention_leaves(m).values()
               if kind == "matrix")


def _expert_params(m: DeepseekV2Spec) -> int:
    return 3 * m.hidden * m.ffn


def _assigned_held(m: DeepseekV2Spec) -> float:
    """Assignments per token to the held experts, under even routing."""
    return m.per_token * m.held / m.experts


def _routed_held(m: DeepseekV2Spec, rows: int) -> float:
    """Held experts that at least one of ``rows`` tokens routes to, under
    even routing: each token picks ``per_token`` distinct experts of
    ``experts``, so misses a given one with probability
    ``1 - per_token / experts``."""
    return m.held * (1.0 - (1.0 - m.per_token / m.experts) ** rows)


def _token_matmul_params(m: DeepseekV2Spec) -> float:
    """Weights one token multiplies by, over every layer: attention (the
    absorbed decode multiplies by ``W_kv_b`` once, as the plain form does),
    the dense MLP, the router, the shared experts and its expected share
    of the held experts."""
    moe_layers = m.layers - m.first_dense
    per_moe = (m.hidden * m.experts + m.shared * _expert_params(m)
               + _assigned_held(m) * _expert_params(m))
    return (m.layers * _attn_params(m) + m.first_dense * 3 * m.hidden * m.dense_ffn
            + moe_layers * per_moe)


def kv_bytes_per_token(m: DeepseekV2Spec) -> int:
    """One latent row a layer: c_kv ‖ k_pe."""
    return m.layers * (m.kv_lora_rank + m.qk_rope) * flops.BF16


def attn_flops(m: DeepseekV2Spec, q_len: int, k_len: float) -> float:
    """Absorbed attention: each head's query of width R + Dr against each
    latent row, and the weighted sum of the rows' R columns."""
    R = m.kv_lora_rank
    return 2.0 * m.heads * (R + m.qk_rope + R) * m.layers * q_len * k_len


def expert_step(m: DeepseekV2Spec, rows: int) -> Dict[str, float]:
    """The held experts' part of one decode step of ``rows`` tokens, all
    MoE layers: the FLOPs of the expected ``rows · per_token · held /
    experts`` assignments, and the bytes of the held experts some token
    routes to, under even routing (``_routed_held``)."""
    moe_layers = m.layers - m.first_dense
    return {"flops": 2.0 * rows * _assigned_held(m) * _expert_params(m) * moe_layers,
            "bytes": _routed_held(m, rows) * _expert_params(m) * moe_layers * flops.BF16}


def decode_step(m: DeepseekV2Spec, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step over active rows whose latent cache holds
    ``contexts`` tokens before the step: every weight but the held experts
    read once, the held experts some row routes to (``expert_step``), the
    live latent rows of each row and the new ones.  Routing is assumed even
    (each expert equally likely), which random weights give."""
    rows = len(contexts)
    ops = rows * 2.0 * (_token_matmul_params(m) + m.hidden * m.padded_vocab)
    ops += sum(attn_flops(m, 1, c + 1) for c in contexts)
    experts = m.held * _expert_params(m) * (m.layers - m.first_dense)
    weights = (flops.param_count(m) - experts) * flops.BF16
    kv = (sum(contexts) + rows) * kv_bytes_per_token(m)
    return {"flops": ops, "bytes": float(weights + kv + expert_step(m, rows)["bytes"])}


def prefill(m: DeepseekV2Spec, prompt_len: int) -> float:
    """Useful operations of one prompt, in the plain form: every valid
    token through every layer (its expected held-expert assignments),
    causal attention with queries and keys of width Dn + Dr and values of
    width Dv, and the logits of the last position."""
    n = prompt_len
    ops = 2.0 * n * _token_matmul_params(m)
    per_pair = 2.0 * m.heads * (m.qk_nope + m.qk_rope + m.v_head) * m.layers
    return ops + per_pair * n * (n + 1) / 2 + 2.0 * m.hidden * m.padded_vocab


# ------------------------------------------------------------ system check
def check_system(cfg, m: DeepseekV2Spec, cache_len: int) -> None:
    """The system's model must be the configuration file's."""
    factor, orig, fast, slow, ms, ms_all = m.yarn
    have = dict(
        family=cfg.family, layers=cfg.num_layers, hidden=cfg.d_model,
        heads=cfg.num_heads, ffn=cfg.d_ff, vocab=cfg.vocab_size,
        norm=cfg.norm, act=cfg.act, gated=cfg.glu, tied=cfg.tie_embeddings,
        rope_theta=cfg.rope_theta, window=cfg.window,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope=cfg.qk_nope_head_dim,
        qk_rope=cfg.qk_rope_head_dim, v_head=cfg.v_head_dim,
        first_dense=cfg.first_dense, dense_ffn=cfg.dense_d_ff,
        experts=cfg.n_experts, held=cfg.held_experts[1],
        first_held=cfg.held_experts[0], per_token=cfg.top_k,
        shared=cfg.n_shared_experts, norm_topk=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scaling_factor,
        yarn=(cfg.yarn_factor, cfg.yarn_original_max_position,
              cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_mscale,
              cfg.yarn_mscale_all_dim))
    want = dict(
        family="moe", layers=m.layers, hidden=m.hidden, heads=m.heads,
        ffn=m.ffn, vocab=m.vocab, norm="rmsnorm", act="silu", gated=True,
        tied=m.tied, rope_theta=m.rope_theta, window=0,
        kv_lora_rank=m.kv_lora_rank, qk_nope=m.qk_nope, qk_rope=m.qk_rope,
        v_head=m.v_head, first_dense=m.first_dense, dense_ffn=m.dense_ffn,
        experts=m.experts, held=m.held, first_held=m.shard * m.held,
        per_token=m.per_token, shared=m.shared, norm_topk=m.norm_topk,
        routed_scale=m.routed_scale,
        yarn=(factor, orig, fast, slow, ms, ms_all))
    if have != want:
        diff = {k: (have[k], want[k]) for k in have if have[k] != want[k]}
        raise ValueError(f"{m.name}: the system's model differs from the "
                         f"configuration file (system, file): {diff}")
