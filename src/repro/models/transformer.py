"""Decoder-only transformer (dense / MoE / VLM families).

Layers are scan-stacked: every per-layer parameter has a leading ``layers``
dim and the forward pass is one ``lax.scan`` over the stack (small HLO, fast
512-device compiles).  The *gather point* implements the BSP vs futurized
distinction (DESIGN.md §2):

- BSP plan: the whole stacked FSDP-sharded parameter tree is constrained to
  its gathered spec **before** the scan — one bulk all-gather, a global
  barrier, peak memory ∝ all layers;
- futurized plan: each layer's slice is constrained **inside** the scan
  body — XLA overlaps the per-layer all-gather with the previous layer's
  compute (async collectives), and the backward pass reduce-scatters
  per-layer.  This is HPX futurization expressed at the XLA level.

MoE layers route through :mod:`repro.models.moe` (the parcel path); the VLM
family splices stub patch embeddings over the first ``n_patches`` positions.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.plan import ShardingPlan
from repro.models import layers as Lx
from repro.models.moe import moe_ffn, moe_param_specs
from repro.models.params import ParamSpec


# ------------------------------------------------------------------- specs
def _mla_specs(cfg: ModelConfig, L: int, prefix: str) -> Dict[str, ParamSpec]:
    """Latent attention, no q LoRA: ``wq`` (D, H·(Dn+Dr)); ``wkv_a`` (D,
    R+Dr) → c_kv ‖ k_pe; ``kv_norm`` on c_kv; ``wkv_b`` (R, H·(Dn+Dv)),
    per head k_nope ‖ v; ``wo`` (H·Dv, D)."""
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        f"{prefix}ln1": ParamSpec((L, D), ("layers", None), init="ones"),
        f"{prefix}wq": ParamSpec((L, D, H * (Dn + Dr)), ("layers", "embed", "heads")),
        f"{prefix}wkv_a": ParamSpec((L, D, R + Dr), ("layers", "embed", None)),
        f"{prefix}kv_norm": ParamSpec((L, R), ("layers", None), init="ones"),
        f"{prefix}wkv_b": ParamSpec((L, R, H * (Dn + Dv)), ("layers", None, "heads")),
        f"{prefix}wo": ParamSpec((L, H * Dv, D), ("layers", "heads", "embed")),
    }


def _attn_specs(cfg: ModelConfig, L: int, prefix: str) -> Dict[str, ParamSpec]:
    if cfg.mla:
        return _mla_specs(cfg, L, prefix)
    D, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        f"{prefix}ln1": ParamSpec((L, D), ("layers", None), init="ones"),
        f"{prefix}wq": ParamSpec((L, D, H * Dh), ("layers", "embed", "heads")),
        f"{prefix}wk": ParamSpec((L, D, KV * Dh), ("layers", "embed", "kv_heads")),
        f"{prefix}wv": ParamSpec((L, D, KV * Dh), ("layers", "embed", "kv_heads")),
        f"{prefix}wo": ParamSpec((L, H * Dh, D), ("layers", "heads", "embed")),
    }
    if cfg.qkv_bias:
        specs.update({
            f"{prefix}bq": ParamSpec((L, H * Dh), ("layers", "heads"), init="zeros"),
            f"{prefix}bk": ParamSpec((L, KV * Dh), ("layers", "kv_heads"), init="zeros"),
            f"{prefix}bv": ParamSpec((L, KV * Dh), ("layers", "kv_heads"), init="zeros"),
        })
    return specs


def _mlp_specs(cfg: ModelConfig, L: int, prefix: str, d_ff: int) -> Dict[str, ParamSpec]:
    D = cfg.d_model
    specs = {
        f"{prefix}ln2": ParamSpec((L, D), ("layers", None), init="ones"),
        f"{prefix}w_in": ParamSpec((L, D, d_ff), ("layers", "embed", "mlp")),
        f"{prefix}w_out": ParamSpec((L, d_ff, D), ("layers", "mlp", "embed")),
    }
    if cfg.glu:
        specs[f"{prefix}w_gate"] = ParamSpec((L, D, d_ff), ("layers", "embed", "mlp"))
    return specs


def decoder_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    D, V = cfg.d_model, cfg.padded_vocab
    specs: Dict[str, ParamSpec] = {
        "tok_embed": ParamSpec((V, D), ("vocab", "embed"), scale=0.02),
        "final_ln": ParamSpec((D,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    fd = cfg.first_dense
    Lm = cfg.num_layers - fd
    if fd > 0:  # leading dense layers (DeepSeekMoE layer 0)
        d_ff0 = cfg.dense_d_ff or cfg.d_ff
        specs.update(_attn_specs(cfg, fd, "d0/"))
        specs.update(_mlp_specs(cfg, fd, "d0/", d_ff0))
    specs.update(_attn_specs(cfg, Lm, "blk/"))
    if cfg.is_moe:
        specs[f"blk/ln2"] = ParamSpec((Lm, D), ("layers", None), init="ones")
        specs.update(moe_param_specs(cfg, Lm, "blk/moe/"))
    else:
        specs.update(_mlp_specs(cfg, Lm, "blk/", cfg.d_ff))
    return specs


# ------------------------------------------------------------------ helpers
_GATHER_AXIS = "embed"  # the FSDP axis


def _layer_axes(specs: Dict[str, ParamSpec], prefix: str) -> Dict[str, Tuple]:
    """Per-layer logical axes (leading 'layers' dim dropped)."""
    out = {}
    for path, s in specs.items():
        if path.startswith(prefix):
            out[path[len(prefix):]] = tuple(a for a in s.axes if a != "layers")
    return out


def _slice_params(params: Dict[str, jax.Array], prefix: str) -> Dict[str, jax.Array]:
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def _gathered(axes: Tuple) -> Tuple:
    return tuple(None if a == _GATHER_AXIS else a for a in axes)


def gather_constrain(plan: ShardingPlan, tree: Dict[str, jax.Array],
                     axes: Dict[str, Tuple]) -> Dict[str, jax.Array]:
    """Constrain every param to its *gathered* (non-FSDP) spec."""
    return {k: plan.constrain(v, _gathered(axes[k])) for k, v in tree.items()}


def stacked_gather_constrain(plan: ShardingPlan, tree: Dict[str, jax.Array],
                             axes: Dict[str, Tuple]) -> Dict[str, jax.Array]:
    """BSP: gather the whole stack up-front (axes still carry 'layers')."""
    return {
        k: plan.constrain(v, ("layers",) + _gathered(axes[k])) for k, v in tree.items()
    }


# ------------------------------------------------------------------ blocks
def _layer_body(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                lp: Dict[str, jax.Array], positions: jax.Array,
                moe_layer: bool, collect_kv: bool = False,
                valid_len: Optional[jax.Array] = None):
    """One layer over whole sequences.  ``collect_kv`` (prefill) also
    returns what the cache holds of the layer, as a tuple in
    :func:`cache_keys` order, routes MoE layers as serving does (no token
    dropped) and gives the attention each row's ``valid_len``; training
    keeps capacity dispatch."""
    x = plan.constrain(x, ("batch", "seq_sp", None))
    h = Lx.norm(cfg, x, lp["ln1"])
    if cfg.mla:
        attn_out = Lx.mla_attention(cfg, plan, h, lp, "", positions,
                                    return_kv=collect_kv, valid_len=valid_len)
    else:
        attn_out = Lx.attention(cfg, plan, h, lp, "", positions,
                                causal=cfg.causal, window=cfg.window,
                                return_kv=collect_kv, valid_len=valid_len)
    if collect_kv:
        h, kv = attn_out
    else:
        h, kv = attn_out, None
    x = x + h
    h = Lx.norm(cfg, x, lp["ln2"])
    if moe_layer:
        ffn, aux = moe_ffn(cfg, plan, h, lp, "moe/", serve=collect_kv)
    else:
        ffn, aux = Lx.mlp(cfg, plan, h, lp, ""), jnp.zeros((), jnp.float32)
    return x + ffn, aux, kv


def _run_stack(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
               stacked: Dict[str, jax.Array], axes: Dict[str, Tuple],
               positions: jax.Array, moe_layer: bool, collect_kv: bool = False,
               valid_len: Optional[jax.Array] = None):
    """lax.scan over a stacked layer dict; returns (x, aux_sum, stacked
    cache rows: a tuple in :func:`cache_keys` order, or None)."""

    def body(carry, lp):
        x, aux_sum = carry
        if not plan.gather_upfront:  # futurized: per-layer gather point
            lp = gather_constrain(plan, lp, axes)
        x, aux, kv = _layer_body(cfg, plan, x, lp, positions, moe_layer,
                                 collect_kv, valid_len)
        return (x, aux_sum + aux), kv

    body = Lx.remat_wrap(plan, body)
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux, kvs


# ------------------------------------------------------------------ forward
def forward(cfg: ModelConfig, plan: ShardingPlan, params: Dict[str, jax.Array],
            tokens: jax.Array, patches: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) → (logits fp32 (B,S,V), aux_loss)."""
    specs = decoder_param_specs(cfg)
    x = Lx.embed(cfg, plan, params["tok_embed"], tokens)
    if cfg.family == "vlm":
        assert patches is not None, "vlm family requires patch embeddings"
        x = jnp.concatenate([patches.astype(x.dtype), x[:, cfg.n_patches:, :]], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    if cfg.first_dense > 0:
        d0 = _slice_params(params, "d0/")
        a0 = _layer_axes(specs, "d0/")
        if plan.gather_upfront:
            d0 = stacked_gather_constrain(plan, d0, a0)
        x, _, _ = _run_stack(cfg, plan, x, d0, a0, positions, moe_layer=False)

    blk = _slice_params(params, "blk/")
    ax = _layer_axes(specs, "blk/")
    if plan.gather_upfront:  # BSP: one bulk all-gather before the loop
        blk = stacked_gather_constrain(plan, blk, ax)
    x, aux, _ = _run_stack(cfg, plan, x, blk, ax, positions, moe_layer=cfg.is_moe)

    x = Lx.norm(cfg, x, params["final_ln"])
    table = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = Lx.unembed(cfg, plan, x, table, transpose=cfg.tie_embeddings)
    return logits, aux


def loss_fn(cfg: ModelConfig, plan: ShardingPlan, params: Dict[str, jax.Array],
            batch: Dict[str, jax.Array]) -> jax.Array:
    tokens = batch["tokens"]
    logits, aux = forward(cfg, plan, params, tokens[:, :-1],
                          patches=batch.get("patches"))
    labels = tokens[:, 1:]
    mask = None
    if cfg.family == "vlm":  # no next-token loss on image positions
        mask = (jnp.arange(labels.shape[1]) >= cfg.n_patches)[None, :].astype(jnp.float32)
        mask = jnp.broadcast_to(mask, labels.shape)
    ce = Lx.cross_entropy(logits, labels, mask)
    return ce + cfg.router_aux_weight * aux


# -------------------------------------------------------------------- cache
def cache_keys(cfg: ModelConfig, group: str) -> Tuple[str, ...]:
    """The cache arrays of layer group ``group`` ("d0/" or "blk/"): one
    latent array under MLA (``ckv``: c_kv ‖ k_pe of every token), K and V
    otherwise; the leading dense group's names end in 0."""
    suffix = "0" if group == "d0/" else ""
    return tuple(n + suffix for n in (("ckv",) if cfg.mla else ("k", "v")))


def _row_shape(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, width) of one token's cache row in one layer."""
    if cfg.mla:
        return 1, Lx.mla_row_width(cfg)
    return cfg.num_kv_heads, cfg.head_dim


def _groups(cfg: ModelConfig):
    """(prefix, depth) of each layer group, in order."""
    fd = cfg.first_dense
    return ((("d0/", fd),) if fd > 0 else ()) + (("blk/", cfg.num_layers - fd),)


def init_cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract KV-cache pytree for the dry-run / serve engine."""
    KV, Dh = _row_shape(cfg)
    dt = jnp.dtype(cfg.dtype)
    specs = {"pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    for group, L in _groups(cfg):
        for key in cache_keys(cfg, group):
            specs[key] = jax.ShapeDtypeStruct((L, batch, cache_len, KV, Dh), dt)
    return specs


def cache_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    ax = ("layers", "batch", "kv_seq", "kv_heads", None)
    out = {"pos": ("batch",)}
    for group, _ in _groups(cfg):
        out.update({key: ax for key in cache_keys(cfg, group)})
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, jax.Array]:
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in
            init_cache_specs(cfg, batch, cache_len).items()}


def paged_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                      max_batch: int, max_pages_per_req: int
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract *paged* KV-cache pytree: a block pool of ``num_pages`` fixed
    ``page_size`` pages shared by every layer (same page index holds a
    request's tokens in all layers, vLLM-style; each page is head-major,
    ``(KV, page, Dh)``, as the paged decode kernel tiles it), plus per-slot
    page tables and fill positions.  Under MLA each layer group has one
    latent pool of ``(1, page, C)`` pages in place of K and V (C:
    :func:`layers.mla_row_width`).  Memory scales with live tokens, not
    ``max_batch × cache_len``.  The decode step reads the stacked pools in
    place during its layer scan and writes the step's new rows once after
    it (:func:`decode_step_paged`)."""
    KV, Dh = _row_shape(cfg)
    dt = jnp.dtype(cfg.dtype)
    specs = {
        "page_table": jax.ShapeDtypeStruct((max_batch, max_pages_per_req), jnp.int32),
        "pos": jax.ShapeDtypeStruct((max_batch,), jnp.int32),
    }
    for group, L in _groups(cfg):
        for key in cache_keys(cfg, group):
            specs[key] = jax.ShapeDtypeStruct((L, num_pages, KV, page_size, Dh), dt)
    return specs


def _paged_decode_stack(cfg: ModelConfig, plan: ShardingPlan, x: jax.Array,
                        stacked: Dict[str, jax.Array], axes: Dict[str, Tuple],
                        pools: Tuple[jax.Array, ...],
                        page_table: jax.Array, pos: jax.Array,
                        moe_layer: bool):
    """lax.scan over one stacked layer group against its pools
    (L, P, KV, page, Dh): K and V, or one latent pool under MLA; returns
    (x, the new pools).

    The pools are read-only inside the scan, which carries ``x`` and scans
    the layer params and the layer index; its ``ys`` are each layer's new
    rows (L, B, KV, Dh) per pool, written into the pools once after it.
    Scanning the pools as ``xs``/``ys`` instead makes XLA copy each layer's
    slice out and back and copy the whole pools, every step.
    """

    def layer(x, xs):
        lp, idx = xs
        if not plan.gather_upfront:
            lp = gather_constrain(plan, lp, axes)
        h = Lx.norm(cfg, x, lp["ln1"])
        if cfg.mla:
            h, lat = Lx.mla_paged_decode_attention(cfg, plan, h, lp, "",
                                                   pools[0], idx, page_table,
                                                   pos)
            rows = (lat,)
        else:
            h, k, v = Lx.paged_decode_attention(cfg, plan, h, lp, "", *pools,
                                                idx, page_table, pos)
            rows = (k, v)
        x = x + h
        h = Lx.norm(cfg, x, lp["ln2"])
        if moe_layer:
            ffn, _ = moe_ffn(cfg, plan, h, lp, "moe/", serve=True)
        else:
            ffn = Lx.mlp(cfg, plan, h, lp, "")
        return x + ffn, rows

    x, rows = jax.lax.scan(
        layer, x, (stacked, jnp.arange(pools[0].shape[0], dtype=jnp.int32)))
    return x, tuple(Lx.write_paged_rows(pool, r, page_table, pos)
                    for pool, r in zip(pools, rows))


def decode_step_paged(cfg: ModelConfig, plan: ShardingPlan,
                      params: Dict[str, jax.Array],
                      cache: Dict[str, jax.Array], token: jax.Array
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step against the paged cache (see paged_cache_specs).
    token: (B, 1) int32 → (logits (B,V) fp32, new cache).  The pools are
    read in place by every layer and the step's new K/V written once after
    each layer scan (:func:`_paged_decode_stack`)."""
    specs = decoder_param_specs(cfg)
    pos = cache["pos"]
    pt = cache["page_table"]
    x = Lx.embed(cfg, plan, params["tok_embed"], token)
    new_cache = dict(cache)

    for group, _ in _groups(cfg):
        stacked = _slice_params(params, group)
        ax = _layer_axes(specs, group)
        if group == "blk/" and plan.gather_upfront:
            stacked = stacked_gather_constrain(plan, stacked, ax)
        keys = cache_keys(cfg, group)
        x, pools = _paged_decode_stack(
            cfg, plan, x, stacked, ax, tuple(cache[k] for k in keys), pt, pos,
            cfg.is_moe and group == "blk/")
        new_cache.update(zip(keys, pools))
    new_cache["pos"] = pos + 1

    x = Lx.norm(cfg, x, params["final_ln"])
    table = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = Lx.unembed(cfg, plan, x, table, transpose=cfg.tie_embeddings)
    return logits[:, 0, :], new_cache


def _decode_layer(cfg: ModelConfig, plan: ShardingPlan, x, lp, caches, pos,
                  moe_layer: bool):
    """One layer of one decode step against dense per-slot caches (a tuple
    in :func:`cache_keys` order); returns (x, the new caches)."""
    h = Lx.norm(cfg, x, lp["ln1"])
    if cfg.mla:
        h, c = Lx.mla_decode_attention(cfg, plan, h, lp, "", caches[0], pos)
        caches = (c,)
    else:
        h, kc, vc = Lx.decode_attention(cfg, plan, h, lp, "", *caches, pos,
                                        window=cfg.window)
        caches = (kc, vc)
    x = x + h
    h = Lx.norm(cfg, x, lp["ln2"])
    if moe_layer:
        ffn, _ = moe_ffn(cfg, plan, h, lp, "moe/", serve=True)
    else:
        ffn = Lx.mlp(cfg, plan, h, lp, "")
    return x + ffn, caches


def decode_step(cfg: ModelConfig, plan: ShardingPlan, params: Dict[str, jax.Array],
                cache: Dict[str, jax.Array], token: jax.Array
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. token: (B, 1) int32 → (logits (B,V) fp32, new cache)."""
    specs = decoder_param_specs(cfg)
    pos = cache["pos"]
    x = Lx.embed(cfg, plan, params["tok_embed"], token)
    new_cache = dict(cache)

    for group, _ in _groups(cfg):
        stacked = _slice_params(params, group)
        ax = _layer_axes(specs, group)
        if group == "blk/" and plan.gather_upfront:
            stacked = stacked_gather_constrain(plan, stacked, ax)
        moe_layer = cfg.is_moe and group == "blk/"

        def body(x, xs, ax=ax, moe_layer=moe_layer):
            lp, caches = xs
            if not plan.gather_upfront:
                lp = gather_constrain(plan, lp, ax)
            return _decode_layer(cfg, plan, x, lp, caches, pos, moe_layer)

        keys = cache_keys(cfg, group)
        x, caches = jax.lax.scan(body, x, (stacked, tuple(cache[k] for k in keys)))
        new_cache.update(zip(keys, caches))
    new_cache["pos"] = pos + 1

    x = Lx.norm(cfg, x, params["final_ln"])
    table = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = Lx.unembed(cfg, plan, x, table, transpose=cfg.tie_embeddings)
    return logits[:, 0, :], new_cache


def prefill(cfg: ModelConfig, plan: ShardingPlan, params: Dict[str, jax.Array],
            tokens: jax.Array, patches: Optional[jax.Array] = None,
            cache_len: Optional[int] = None,
            valid_len: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-pass forward + KV-cache collection.

    Returns (last-position logits (B, V) fp32, cache).  K/V are collected as
    scan outputs of the same stack pass (``collect_kv``) — no second pass.

    ``valid_len`` (scalar or (B,) int32) supports right-padded prompts (the
    serve engine pads to static buckets so admission never recompiles):
    logits are taken at position ``valid_len - 1`` instead of ``S - 1`` and
    the cache ``pos`` starts at ``valid_len``.  Causality makes the pad
    positions inert — no valid token attends to them; the attention is
    given ``valid_len`` too, and on TPU skips the pad (whose rows it leaves
    zero, so the pad's cache rows differ from an unpadded run's, unread).
    """
    specs = decoder_param_specs(cfg)
    B, S = tokens.shape
    T = cache_len or S
    x = Lx.embed(cfg, plan, params["tok_embed"], tokens)
    if cfg.family == "vlm" and patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x[:, cfg.n_patches:, :]], axis=1)
    positions = jnp.arange(S, dtype=jnp.int32)
    cache = init_cache(cfg, B, T)
    vl = None if valid_len is None else jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32), (B,))

    for group, _ in _groups(cfg):
        stacked = _slice_params(params, group)
        ax = _layer_axes(specs, group)
        if plan.gather_upfront:
            stacked = stacked_gather_constrain(plan, stacked, ax)
        x, _, rows = _run_stack(cfg, plan, x, stacked, ax, positions,
                                moe_layer=cfg.is_moe and group == "blk/",
                                collect_kv=True, valid_len=vl)
        for key, r in zip(cache_keys(cfg, group), rows):
            cache[key] = _place(cache[key], r)
    if vl is None:
        cache["pos"] = jnp.full((B,), S, jnp.int32)
        x_last = x[:, -1:, :]
    else:
        cache["pos"] = vl
        idx = jnp.clip(vl - 1, 0, S - 1)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)
    x_last = Lx.norm(cfg, x_last, params["final_ln"])
    table = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = Lx.unembed(cfg, plan, x_last, table, transpose=cfg.tie_embeddings)
    return logits[:, 0, :], cache


def _place(buf: jax.Array, kv: jax.Array) -> jax.Array:
    """Write (L,B,S,KV,Dh) prefill rows into the (L,B,T,KV,Dh) cache buffer."""
    return jax.lax.dynamic_update_slice_in_dim(buf, kv.astype(buf.dtype), 0, axis=2)
