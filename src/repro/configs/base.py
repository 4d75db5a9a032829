"""Config system: architecture configs, input-shape cells, and the registry.

Each assigned architecture lives in ``src/repro/configs/<id>.py`` exposing
``full_config()`` (the exact published dims) and ``smoke_config()`` (a
reduced same-family config for CPU smoke tests).  The registry maps
``--arch <id>`` to those.

The four assigned input-shape cells are global (``SHAPES``); per-arch
applicability (e.g. ``long_500k`` only for sub-quadratic families) is
resolved by :func:`cells_for`.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention / embedding flags
    qkv_bias: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "silu"  # silu | gelu
    glu: bool = True  # gated MLP (SwiGLU/GeGLU) vs plain 2-layer
    causal: bool = True
    window: int = 0  # >0: sliding-window (local) attention
    learned_pos: bool = False  # learned absolute positions (whisper decoder)
    max_position: int = 0  # size of learned position table (0 = max seq)
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    first_dense: int = 0  # leading dense FFN layers (DeepSeekMoE)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    dense_d_ff: int = 0  # d_ff for the leading dense layers / shared experts base
    norm_topk_prob: bool = True  # renormalise the top-k routing weights
    routed_scaling_factor: float = 1.0  # times every routed expert's weight
    # the routed experts this layer holds: share ``index`` of ``count`` equal
    # shares of ``n_experts`` (expert parallelism); the router keeps all
    expert_shard: Tuple[int, int] = (0, 1)
    # multi-head latent attention (DeepSeek-V2); kv_lora_rank 0 = off
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling (DeepSeek-V2 ``rope_scaling``); factor 0 = off
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # SSM (Mamba-2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_ngroups: int = 1
    ssm_conv: int = 4
    # Hybrid (RecurrentGemma / Griffin)
    block_pattern: Tuple[str, ...] = ()  # e.g. ("rec", "rec", "attn")
    lru_width: int = 0
    # Enc-dec (Whisper)
    enc_layers: int = 0
    dec_layers: int = 0
    # VLM (InternVL2)
    n_patches: int = 0
    # numerics / kernels
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    # full-sequence attention (prefill, training): xla | pallas (flash
    # kernel; interpret on CPU).  Paged decode picks its path by backend.
    attn_impl: str = "xla"

    def __post_init__(self):
        # a configuration file gives the shard as a JSON list
        object.__setattr__(self, "expert_shard", tuple(self.expert_shard))
        i, n = self.expert_shard
        if not 0 <= i < n or (self.n_experts and self.n_experts % n):
            raise ValueError(f"expert_shard {self.expert_shard} does not "
                             f"divide {self.n_experts} experts")

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def held_experts(self) -> Tuple[int, int]:
        """[first, first + count) of the routed experts this layer holds."""
        i, n = self.expert_shard
        per = self.n_experts // n
        return i * per, per

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to 128 so the TP axis always divides it (embedding
        tables and logits shard on every mesh; padded logit columns are
        masked to -inf in ``unembed`` — exact semantics preserved)."""
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this family decode at 500k context? SSM: O(1) state.
        Hybrid: O(window) local attention + O(1) recurrent state."""
        return self.family in ("ssm", "hybrid")

    @property
    def moe_layer_count(self) -> int:
        return self.num_layers - self.first_dense if self.is_moe else 0


@dataclass(frozen=True)
class ShapeCell:
    """One assigned (input shape) cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS: List[str] = [
    "whisper_small",
    "mamba2_780m",
    "qwen25_3b",
    "starcoder2_3b",
    "granite_34b",
    "starcoder2_15b",
    "deepseek_moe_16b",
    "deepseek_v2_lite",
    "granite_moe_3b_a800m",
    "recurrentgemma_2b",
    "internvl2_2b",
]

# accept dashed spellings on the CLI
_ALIASES = {a.replace("_", "-"): a for a in ARCH_IDS}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    arch = _ALIASES.get(arch, arch)
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.smoke_config() if smoke else mod.full_config()


def cells_for(cfg: ModelConfig) -> List[str]:
    """Applicable shape cells for an arch (DESIGN.md §4 skips)."""
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic:
        cells.append("long_500k")  # needs sub-quadratic attention
    return cells


def all_cells() -> List[Tuple[str, str]]:
    """Every live (arch, shape) baseline cell."""
    out: List[Tuple[str, str]] = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for cell in cells_for(cfg):
            out.append((arch, cell))
    return out
