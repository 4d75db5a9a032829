"""StarCoder2 (``model_type`` "starcoder2"): the dense decoder with
LayerNorm, a plain GELU (tanh) MLP, and biases as ``use_bias`` says."""

from dense_decoder import *  # noqa: F401,F403  (the family's hooks)
from dense_decoder import read_spec


def spec(name: str, conf: dict):
    return read_spec(name, conf, norm="layer", eps_key="norm_epsilon",
                     gated=False, act="gelu_tanh",
                     qkv_bias=bool(conf["use_bias"]))
