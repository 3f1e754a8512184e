"""deepseek-v2-lite-16b [mla_moe] — MLA kv_lora=512, shared+routed MoE.

27L d_model=2048 16H vocab=102400; layer 0 a dense SwiGLU MLP of width
10944, layers 1-26 64 routed experts of width 1408, top-6 by softmax
score (greedy, not renormalised), plus 2 shared experts fused to width
2816.  YaRN rope (factor 40 over 4096 original positions) on the
64-wide roped part.  MLA caches the compressed KV latent
(kv_lora_rank + qk_rope_head_dim = 576 dims/token) instead of full K/V.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="mla_moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,   # MLA: per-head K/V reconstructed from shared latent
    d_ff=10944,      # the dense first layer's MLP
    vocab=102400,
    head_dim=128,
    rope_theta=10000.0,
    max_seq=163840,
    norm_eps=1e-6,
    mla=MLAConfig(
        kv_lora_rank=512,
        q_lora_rank=0,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        n_shared=2,
        d_shared=2816,   # 2 shared experts fused: 2 * 1408
        first_dense=1,
        d_dense_ff=10944,
    ),
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
)
