"""Moonlight-16B-A3B (DeepSeek-V3 architecture) — 27 layers of latent
attention (16 heads, no q compression, 128 + 64 rotary key dims, a 512-wide
kv latent); layer 0 a dense SwiGLU of 11264, layers 1-26 64 routed SwiGLU
experts of 1408 (top-6, sigmoid scores plus a correction bias, weights
normalised and scaled by 2.446) and two shared experts; untied 163840-token
head. Held here: experts 0-7 of 64 in every expert layer, a chip's share of
a v5litepod-8 that splits each expert layer 8 ways.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope="full",
    rope_theta=50000.0,
    norm="rmsnorm",
    norm_eps=1e-5,
    mlp="swiglu",
    tie_embeddings=False,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        shared_expert=True,
        d_ff_shared=2 * 1408,
        router="sigmoid_bias",
        routed_scale=2.446,
        held_experts=8,
        first_dense_layers=1,
    ),
    max_seq_len=8192,
    citation="hf:moonshotai/Moonlight-16B-A3B",
)
