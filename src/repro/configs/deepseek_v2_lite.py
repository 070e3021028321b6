"""deepseek-v2-lite — MLA kv_lora=512 without q compression, 2 shared + 64
routed experts top-6 [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

[moe] 27L d_model=2048 16H vocab=102400 (untied), rms_norm_eps 1e-6.
MLA: kv_lora_rank=512, q_lora_rank none, qk_nope=128, qk_rope=64,
v_head=128; rotary theta 10000 with YaRN (factor 40, original 4096, beta
32/1, mscale and mscale_all_dim 0.707), so the softmax scale is
192^-0.5 * (1 + 0.1 * 0.707 * ln 40)^2.  Layer 0 is a dense MLP (d_ff 10944),
layers 1..26 MoE: 64 routed experts of 1408, a softmax router, greedy top-6,
gates not renormalised, routed_scaling_factor 1.
"""
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig, RopeScaling

YARN = RopeScaling(
    factor=40.0,
    original_max_position=4096,
    beta_fast=32.0,
    beta_slow=1.0,
    mscale=0.707,
    mscale_all_dim=0.707,
)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        d_model=2048,
        d_ff=10944,  # the dense layer's width; experts use expert_ff
        vocab_size=102400,
        attention=AttentionConfig(
            num_heads=16,
            num_kv_heads=16,
            head_dim=128,
            kind="mla",
            rope_theta=10000.0,
            rope_scaling=YARN,
            kv_lora_rank=512,
            q_lora_rank=0,
            qk_nope_dim=128,
            qk_rope_dim=64,
            v_head_dim=128,
        ),
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            expert_ff=1408,
            num_shared=2,
            first_dense_layers=1,
            dense_ff=10944,
            norm_topk_prob=False,
            routed_scaling_factor=1.0,
        ),
        tie_embeddings=False,
        norm_eps=1e-6,
        citation="hf:deepseek-ai/DeepSeek-V2-Lite",
    )
