"""Unified model facade: one API across the six families.

``Model(cfg, plan)`` dispatches to the family module and exposes:

    param_specs() / init(rng) / abstract_params()
    loss(params, batch)                  train objective
    prefill(params, inputs)              → (last logits, cache)
    decode(params, cache, token)         → (logits, new cache)
    cache_specs(batch, cache_len, enc_len) / cache_axes()
    batch_specs(cell) / prefill_specs(cell) / decode_specs(cell)
        → ShapeDtypeStruct stand-ins for the dry-run (no allocation)

Modality frontends are stubs per the assignment: ``encdec`` takes
precomputed frame embeddings, ``vlm`` takes precomputed patch embeddings.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.dist.plan import ShardingPlan
from repro.models import encdec, hybrid, ssm_lm, transformer
from repro.models.params import ParamSpec, abstract_params, init_params


class Model:
    def __init__(self, cfg: ModelConfig, plan: ShardingPlan):
        self.cfg = cfg
        self.plan = plan
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            self._m = transformer
            specs = transformer.decoder_param_specs(cfg)
        elif fam == "ssm":
            self._m = ssm_lm
            specs = ssm_lm.lm_param_specs(cfg)
        elif fam == "hybrid":
            self._m = hybrid
            specs = hybrid.hybrid_param_specs(cfg)
        elif fam == "encdec":
            self._m = encdec
            specs = encdec.encdec_param_specs(cfg)
        else:
            raise ValueError(f"unknown family {fam!r}")
        # weights are stored at the config's param dtype (f32 by default;
        # serving passes bf16), whatever the family builders declare
        dt = jnp.dtype(cfg.param_dtype)
        self._specs = {p: replace(s, dtype=dt) for p, s in specs.items()}

    # ---------------------------------------------------------------- params
    def param_specs(self) -> Dict[str, ParamSpec]:
        return self._specs

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        return init_params(self._specs, rng)

    def abstract_params(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return abstract_params(self._specs)

    # ----------------------------------------------------------------- train
    def loss(self, params, batch) -> jax.Array:
        if self.cfg.family == "encdec":
            return encdec.loss_fn(self.cfg, self.plan, params, batch)
        if self.cfg.family in ("ssm",):
            return ssm_lm.loss_fn(self.cfg, self.plan, params, batch)
        if self.cfg.family == "hybrid":
            return hybrid.loss_fn(self.cfg, self.plan, params, batch)
        return transformer.loss_fn(self.cfg, self.plan, params, batch)

    # ----------------------------------------------------------------- serve
    def prefill(self, params, inputs: Dict[str, jax.Array],
                cache_len: Optional[int] = None,
                valid_len: Optional[jax.Array] = None):
        """``cache_len`` is static (jit with static_argnums if passed).
        ``valid_len`` (traced) supports right-padded prompts — transformer
        families only (the serve engine's bucketed admission)."""
        cfg, plan = self.cfg, self.plan
        if cfg.family == "encdec":
            return encdec.prefill(cfg, plan, params, inputs["enc"], inputs["tokens"],
                                  cache_len=cache_len)
        if cfg.family == "ssm":
            return ssm_lm.prefill(cfg, plan, params, inputs["tokens"])
        if cfg.family == "hybrid":
            return hybrid.prefill(cfg, plan, params, inputs["tokens"])
        return transformer.prefill(cfg, plan, params, inputs["tokens"],
                                   patches=inputs.get("patches"),
                                   cache_len=cache_len, valid_len=valid_len)

    def decode(self, params, cache, token):
        cfg, plan = self.cfg, self.plan
        if cfg.family == "encdec":
            return encdec.decode_step(cfg, plan, params, cache, token)
        if cfg.family == "ssm":
            return ssm_lm.decode_step(cfg, plan, params, cache, token)
        if cfg.family == "hybrid":
            return hybrid.decode_step(cfg, plan, params, cache, token)
        return transformer.decode_step(cfg, plan, params, cache, token)

    @property
    def supports_paged(self) -> bool:
        """Paged KV serving applies to families with a dense KV cache; SSM /
        hybrid / encdec carry recurrent or ring-buffer state instead."""
        return self.cfg.family in ("dense", "moe", "vlm")

    def decode_paged(self, params, cache, token):
        """One decode step against a block-pool paged cache
        (:func:`repro.models.transformer.paged_cache_specs` layout)."""
        assert self.supports_paged, self.cfg.family
        return transformer.decode_step_paged(self.cfg, self.plan, params,
                                             cache, token)

    def paged_cache_specs(self, num_pages: int, page_size: int,
                          max_batch: int, max_pages_per_req: int):
        assert self.supports_paged, self.cfg.family
        return transformer.paged_cache_specs(self.cfg, num_pages, page_size,
                                             max_batch, max_pages_per_req)

    def cache_specs(self, batch: int, cache_len: int, enc_len: Optional[int] = None):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.init_cache_specs(cfg, batch, cache_len, enc_len or cache_len)
        if cfg.family == "ssm":
            return ssm_lm.init_cache_specs(cfg, batch, cache_len)
        if cfg.family == "hybrid":
            return hybrid.init_cache_specs(cfg, batch, cache_len)
        return transformer.init_cache_specs(cfg, batch, cache_len)

    def cache_axes(self):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.cache_axes(cfg)
        if cfg.family == "ssm":
            return ssm_lm.cache_axes(cfg)
        if cfg.family == "hybrid":
            return hybrid.cache_axes(cfg)
        return transformer.cache_axes(cfg)

    # ------------------------------------------------------- dry-run inputs
    def batch_specs(self, cell: ShapeCell) -> Dict[str, jax.ShapeDtypeStruct]:
        """Training-batch stand-ins for a shape cell."""
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        dt = jnp.dtype(cfg.dtype)
        specs = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), dt)
        if cfg.family == "encdec":
            specs["enc"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        return specs

    def batch_axes(self) -> Dict[str, Tuple]:
        cfg = self.cfg
        ax = {"tokens": ("batch", "seq")}
        if cfg.family == "vlm":
            ax["patches"] = ("batch", "seq", None)
        if cfg.family == "encdec":
            ax["enc"] = ("batch", "seq", None)
        return ax

    def prefill_specs(self, cell: ShapeCell) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        dt = jnp.dtype(cfg.dtype)
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), dt)
        if cfg.family == "encdec":
            specs["enc"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        return specs

    def decode_specs(self, cell: ShapeCell) -> Tuple[Dict[str, Any], jax.ShapeDtypeStruct]:
        """(cache specs, token spec) for a decode cell: one new token against
        a KV cache of ``cell.seq_len``."""
        B, S = cell.global_batch, cell.seq_len
        cache = self.cache_specs(B, S, enc_len=S)
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return cache, token


def build_model(cfg: ModelConfig, plan: ShardingPlan) -> Model:
    return Model(cfg, plan)
