"""Mixture-of-Experts FFN: shared + routed experts, top-k, capacity dispatch.

This layer is the flagship *parcel* user (DESIGN.md P4): a token assigned to
an expert is an active message — the token (arguments) travels to the expert
"locality" (its shard on the model axis), compute happens *at the data*, and
the result returns through the combine path.  Dispatch-time load balance
(capacity factor + aux loss) replaces HPX's dynamic work stealing, which has
no on-device analogue (DESIGN.md §8.3).

Dispatch is **grouped-local** (GShard-style groups == data shards): tokens
are viewed as (G, T/G, D) with G = the batch-sharding degree of the active
mesh, routing ranks are computed per group with a one-hot cumsum (no global
sort), and the capacity buffers are (G, E, C, D) built by *batched* scatters
(vmap over G) — the scatter's batch dim aligns with the data axis, so GSPMD
keeps dispatch entirely local to each shard.  The EXPERIMENTS.md §Perf log
records the win: the naive global-scatter formulation forced full-buffer
all-reduces over the data axis (granite-moe train: 559 s collective term).

Capacity is per group (C = cf·T_loc·k/E), the standard per-shard semantics
of production EP systems.  That is training's dispatch; serving drops no
token (:func:`moe_ffn` with ``serve=True``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.plan import ShardingPlan, _active_mesh
from repro.models.layers import act_fn, cdtype
from repro.models.params import ParamSpec


def moe_param_specs(cfg: ModelConfig, L: int, prefix: str) -> Dict[str, ParamSpec]:
    """Stacked (L, …) specs for the routed-expert FFN of ``L`` layers: the
    router over all ``n_experts``, the weights of the experts this layer
    holds (``cfg.held_experts``), and the shared experts."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Eh = cfg.held_experts[1]
    specs: Dict[str, ParamSpec] = {
        f"{prefix}router": ParamSpec((L, D, E), ("layers", "embed", None)),
        f"{prefix}w_in": ParamSpec((L, Eh, D, F), ("layers", "experts", "embed", "mlp")),
        f"{prefix}w_gate": ParamSpec((L, Eh, D, F), ("layers", "experts", "embed", "mlp")),
        f"{prefix}w_out": ParamSpec((L, Eh, F, D), ("layers", "experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts > 0:
        Fs = cfg.n_shared_experts * F
        specs.update({
            f"{prefix}shared_w_in": ParamSpec((L, D, Fs), ("layers", "embed", "mlp")),
            f"{prefix}shared_w_gate": ParamSpec((L, D, Fs), ("layers", "embed", "mlp")),
            f"{prefix}shared_w_out": ParamSpec((L, Fs, D), ("layers", "mlp", "embed")),
        })
    return specs


def _group_count(T: int) -> int:
    """Dispatch groups = batch-sharding degree of the active mesh."""
    mesh = _active_mesh()
    if mesh is None:
        return 1
    g = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            g *= mesh.shape[ax]
    return g if g > 1 and T % g == 0 else 1


def route(cfg: ModelConfig, x: jax.Array, router: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax scoring over all ``n_experts`` and greedy top-k, in float32
    as the published gates compute it.  x: (..., D) → (probs (..., E),
    weights (..., K), expert ids (..., K)); the weights are renormalised
    over the top k only where ``cfg.norm_topk_prob`` says so, then scaled
    by ``cfg.routed_scaling_factor``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return probs, w * cfg.routed_scaling_factor, idx


def _shared(cfg: ModelConfig, x: jax.Array, p: Dict[str, jax.Array],
            prefix: str) -> jax.Array:
    """The shared experts, for every token: x (..., D) → (..., D)."""
    dt = cdtype(cfg)
    with jax.named_scope("moe_shared"):
        h = x @ p[f"{prefix}shared_w_in"].astype(dt)
        g = x @ p[f"{prefix}shared_w_gate"].astype(dt)
        return (act_fn(cfg, g) * h) @ p[f"{prefix}shared_w_out"].astype(dt)


def _held_dense(cfg: ModelConfig, x: jax.Array, w: jax.Array, idx: jax.Array,
                p: Dict[str, jax.Array], prefix: str) -> jax.Array:
    """Every held expert over every token, weighted by the routing (zero
    where a token does not route to it): each held expert's weights are
    read once.  The decode step's form (one token a row).  x: (T, D)."""
    dt = cdtype(cfg)
    lo, Eh = cfg.held_experts
    comb = jnp.sum(jax.nn.one_hot(idx - lo, Eh, dtype=jnp.float32)
                   * w[..., None], axis=-2)  # (T, Eh); absent ids one-hot 0
    h = jnp.einsum("td,edf->etf", x, p[f"{prefix}w_in"].astype(dt))
    g = jnp.einsum("td,edf->etf", x, p[f"{prefix}w_gate"].astype(dt))
    y = jnp.einsum("etf,efd->etd", act_fn(cfg, g) * h,
                   p[f"{prefix}w_out"].astype(dt))
    return jnp.einsum("te,etd->td", comb, y.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST).astype(dt)


def _held_grouped(cfg: ModelConfig, x: jax.Array, w: jax.Array,
                  idx: jax.Array, p: Dict[str, jax.Array],
                  prefix: str) -> jax.Array:
    """Grouped matmuls over the assignments to held experts: the (token,
    expert) assignments sorted by expert, those to absent experts last and
    outside every group, then ``ragged_dot`` per held expert, so the work
    follows the routed assignments (the prefill's form).  The outputs go
    back to (token, k) order by a gather and are summed over k (a
    scatter-add onto the tokens took half of this layer's prefill time on
    a TPU v5e).  x: (T, D)."""
    dt = cdtype(cfg)
    T, K = idx.shape
    lo, Eh = cfg.held_experts
    local = idx.reshape(T * K) - lo
    held = (local >= 0) & (local < Eh)
    group = jnp.where(held, local, Eh)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
    xs = jnp.take(x, order // K, axis=0).astype(dt)
    h = jax.lax.ragged_dot(xs, p[f"{prefix}w_in"].astype(dt), sizes)
    g = jax.lax.ragged_dot(xs, p[f"{prefix}w_gate"].astype(dt), sizes)
    y = jax.lax.ragged_dot(act_fn(cfg, g) * h, p[f"{prefix}w_out"].astype(dt),
                           sizes)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, K, -1)
    # rows past the held groups belong to no expert: masked, not weighted
    y = jnp.where(held.reshape(T, K, 1), y, 0)
    return jnp.einsum("tk,tkd->td", w, y, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST).astype(dt)


def moe_ffn(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
            p: Dict[str, jax.Array], prefix: str = "",
            serve: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (out (B,S,D), aux_loss scalar).

    The layer holds the routed experts ``cfg.held_experts`` and routes over
    all ``n_experts``: assignments to experts it does not hold add nothing
    (on one chip of an expert-parallel deployment the exchange with the
    other chips is absent).  The shared experts run on every token.

    ``serve=True`` drops no token: one token a row (decode) runs every held
    expert over the batch, longer sequences (prefill) run grouped matmuls
    over the sorted assignments; aux is 0.  Otherwise (training) capacity
    dispatch as below.  Named scopes: ``router``, ``moe_experts`` (the held
    experts' matmuls and the combine), ``moe_shared``.
    """
    if serve:
        B, S, D = x.shape
        xt = x.reshape(B * S, D)
        with jax.named_scope("router"):
            _, w, idx = route(cfg, xt, p[f"{prefix}router"])
        with jax.named_scope("moe_experts"):
            y = (_held_dense if S == 1 else _held_grouped)(cfg, xt, w, idx, p,
                                                            prefix)
        if cfg.n_shared_experts > 0:
            y = y + _shared(cfg, xt, p, prefix)
        return y.reshape(B, S, D), jnp.zeros((), jnp.float32)
    return _moe_capacity(cfg, plan, x, p, prefix)


def _moe_capacity(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                  p: Dict[str, jax.Array], prefix: str
                  ) -> Tuple[jax.Array, jax.Array]:
    """Training's grouped-local capacity dispatch (module docstring); tokens
    past an expert's capacity are dropped."""
    dt = cdtype(cfg)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    lo, Eh = cfg.held_experts
    T = B * S
    G = _group_count(T)
    TL = T // G  # tokens per group (== per data shard on the production mesh)
    xt = plan.constrain(x.reshape(G, TL, D), ("batch", None, None))

    # ---- routing (fp32, local per group) ----------------------------------
    with jax.named_scope("router"):
        probs, gate_w, gate_i = route(cfg, xt, p[f"{prefix}router"])  # (G,TL,K)

    # Switch-style load-balance aux loss: E · Σ_e f_e · P_e (global mean)
    f_e = jnp.mean(jax.nn.one_hot(gate_i, E, dtype=jnp.float32), axis=(0, 1, 2))
    P_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e * P_e)

    # ---- grouped-local dispatch (parcel routing) ---------------------------
    A = TL * K  # assignments per group
    # capacity floor: small-T (decode) batches must never drop — a dropped
    # parcel at decode time corrupts a live request
    C = max(int(cfg.capacity_factor * A / E), min(A, 16), 1)
    flat_e = gate_i.reshape(G, A) - lo  # held experts: 0 .. Eh-1
    held = (flat_e >= 0) & (flat_e < Eh)
    tok_of = jnp.broadcast_to(
        jnp.repeat(jnp.arange(TL), K)[None, :], (G, A))
    # rank within (group, expert): one-hot cumsum — local, no global sort
    onehot = (flat_e[:, :, None] == jnp.arange(Eh)[None, None, :]).astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1),
                              jnp.clip(flat_e, 0, Eh - 1)[:, :, None],
                              axis=2)[:, :, 0] - 1  # (G, A)
    keep = held & (pos < C)
    slot = jnp.where(keep, flat_e * C + pos, Eh * C)  # trap row for drops

    updates = jnp.take_along_axis(xt, tok_of[:, :, None], axis=1).astype(dt)
    buf = jax.vmap(lambda s, u: jnp.zeros((Eh * C + 1, D), dt).at[s].add(u))(
        slot, updates)  # batched scatter: group dim == data shard, stays local
    buf = plan.constrain(buf[:, : Eh * C].reshape(G, Eh, C, D),
                         ("batch", "experts", "expert_cap", None))

    # ---- expert GEMMs at the data (model-axis shards) ----------------------
    with jax.named_scope("moe_experts"):
        h = jnp.einsum("gecd,edf->gecf", buf, p[f"{prefix}w_in"].astype(dt))
        g = jnp.einsum("gecd,edf->gecf", buf, p[f"{prefix}w_gate"].astype(dt))
        h = act_fn(cfg, g) * h
        out_buf = jnp.einsum("gecf,efd->gecd", h, p[f"{prefix}w_out"].astype(dt))
        out_buf = plan.constrain(out_buf, ("batch", "experts", "expert_cap", None))

        # ---- combine (return parcels, batched gather + scatter) ------------
        flat_out = jnp.concatenate(
            [out_buf.reshape(G, Eh * C, D), jnp.zeros((G, 1, D), dt)], axis=1)
        y_assign = jnp.take_along_axis(flat_out, slot[:, :, None], axis=1)
        y_assign = y_assign.astype(jnp.float32) * gate_w.reshape(G, A)[:, :, None]
        y = jax.vmap(lambda t, ya: jnp.zeros((TL, D), jnp.float32).at[t].add(ya))(
            tok_of, y_assign).astype(dt)
        y = plan.constrain(y, ("batch", None, None))

    # ---- shared experts (dense path, always-on) ----------------------------
    if cfg.n_shared_experts > 0:
        y = y + _shared(cfg, xt, p, prefix)

    return y.reshape(B, S, D), aux
