"""deepseek-v2-lite [hf:deepseek-ai/DeepSeek-V2-Lite] — multi-head latent
attention (MLA) + fine-grained MoE, 2 shared + 64 routed experts top-6.

27L (layer 0 dense FFN d_ff=10944), d_model=2048, 16 heads; MLA with
kv_lora_rank=512, no q LoRA, per head qk_nope 128 ‖ qk_rope 64 and v 128;
YaRN RoPE (factor 40 over 4096 original positions, theta 1e4); per-expert
d_ff=1408, softmax routing, greedy top-6, top-k weights not renormalised;
vocab=102400, untied head, SwiGLU, RMSNorm (eps 1e-6).  The decode cache
is one latent row per token and layer (c_kv 512 ‖ k_pe 64).
"""
from repro.configs.base import ModelConfig

_MLA_YARN = dict(
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10000.0, yarn_factor=40.0,
    yarn_original_max_position=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707)


def full_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek_v2_lite", family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=192, d_ff=1408, vocab_size=102400,
        n_experts=64, n_shared_experts=2, top_k=6, first_dense=1,
        dense_d_ff=10944, norm_topk_prob=False, routed_scaling_factor=1.0,
        **_MLA_YARN,
    )


def smoke_config() -> ModelConfig:
    """Same family at CPU size: MLA with YaRN (the published rope dims and
    scaling, so the ramp is the published one), one dense layer, MoE."""
    return ModelConfig(
        name="deepseek_v2_lite_smoke", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=80, d_ff=32, vocab_size=512,
        n_experts=8, n_shared_experts=2, top_k=3, first_dense=1,
        dense_d_ff=128, norm_topk_prob=False, routed_scaling_factor=1.0,
        **{**_MLA_YARN, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "v_head_dim": 16},
    )
