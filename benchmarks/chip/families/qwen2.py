"""Qwen2 (``model_type`` "qwen2"): the dense decoder with RMSNorm, SwiGLU
and biases on Q, K and V; the head is tied or not as
``tie_word_embeddings`` says (Qwen2 models above 3B ship untied)."""

from dense_decoder import *  # noqa: F401,F403  (the family's hooks)
from dense_decoder import read_spec


def spec(name: str, conf: dict):
    return read_spec(name, conf, norm="rms", eps_key="rms_norm_eps",
                     gated=True, act="silu", qkv_bias=True)
