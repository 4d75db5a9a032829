"""A configuration file's model, read from its published ``config.json`` keys.

``ModelSpec`` is what every family shares: the sizes, norm, activation and
rotary settings that the reference and the operation counts need.  A family
module (``families/<model_type>.py``, found by ``manifest.family``) reads
its own configuration keys into it, and may extend it with fields of its
own.  This reads only the configuration file and imports nothing of the
system under test.
"""

from __future__ import annotations

from dataclasses import dataclass

import manifest


@dataclass(frozen=True)
class ModelSpec:
    name: str
    model_type: str    # names the family module
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
    """The spec of configuration ``name``, read by its family's module; a
    ``model_type`` with no family file is an error that names the file."""
    return manifest.family(cfg["model_type"]).spec(name, cfg)
