"""A configuration file's model, read from its published ``config.json`` keys.

The benchmark's own reading of a dense decoder: the sizes, norm, activation
and rotary settings that the reference and the operation counts need.  It
reads only the configuration file and imports nothing of the system under
test.
"""

from __future__ import annotations

from dataclasses import dataclass

# model_type -> how that family spells its norm, MLP and biases
_FAMILIES = {
    "qwen2": dict(norm="rms", eps_key="rms_norm_eps", gated=True,
                  act="silu", qkv_bias=True),
    "starcoder2": dict(norm="layer", eps_key="norm_epsilon", gated=False,
                       act="gelu_tanh", qkv_bias=None),
}


@dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    norm: str          # "rms" | "layer"
    eps: float
    gated: bool        # SwiGLU (w_gate, w_in, w_out) vs plain 2-layer MLP
    act: str           # "silu" | "gelu_tanh"
    qkv_bias: bool
    rope_theta: float
    tied: bool
    window: int        # 0: full causal attention

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding table as served (a multiple of 128)."""
        return -(-self.vocab // 128) * 128


def from_config(name: str, cfg: dict) -> ModelSpec:
    fam = _FAMILIES.get(cfg["model_type"])
    if fam is None:
        raise ValueError(f"{name}: no reference for model_type "
                         f"{cfg['model_type']!r}")
    heads = int(cfg["num_attention_heads"])
    hidden = int(cfg["hidden_size"])
    qkv_bias = fam["qkv_bias"]
    if qkv_bias is None:
        qkv_bias = bool(cfg["use_bias"])
    window = 0
    if cfg.get("sliding_window") and cfg.get("use_sliding_window", True):
        window = int(cfg["sliding_window"])
    return ModelSpec(
        name=name, layers=int(cfg["num_hidden_layers"]), hidden=hidden,
        heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or hidden // heads),
        ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        norm=fam["norm"], eps=float(cfg[fam["eps_key"]]),
        gated=fam["gated"], act=fam["act"], qkv_bias=qkv_bias,
        rope_theta=float(cfg["rope_theta"]),
        tied=bool(cfg.get("tie_word_embeddings", False)), window=window)
