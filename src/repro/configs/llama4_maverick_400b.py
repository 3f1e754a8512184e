"""llama4-maverick-400b-a17b [moe] — MoE, early fusion.

48L d_model=5120 40H (GQA kv=8) d_ff=8192 vocab=202048, MoE 128e top-1.
[hf:meta-llama/Llama-4-Scout-17B-16E; unverified]

Note: Llama-4 interleaves dense and MoE FFN layers; we model every layer
as MoE (top-1 routed + one always-on shared expert of d_ff), which matches
the assigned spec's "MoE 128e top-1" and keeps the layer stack homogeneous
for lax.scan.  See DESIGN.md §Arch-notes.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="llama4-maverick-400b-a17b",
    family="moe",
    n_layers=48,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    d_ff=8192,
    vocab=202048,
    head_dim=128,
    rope_theta=500000.0,
    max_seq=524288,
    moe=MoEConfig(
        n_experts=128,
        top_k=1,
        d_expert=8192,
        n_shared=1,
        d_shared=8192,
    ),
    source="hf:meta-llama/Llama-4-Scout-17B-16E; unverified",
)
