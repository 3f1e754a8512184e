"""Architecture registry: ``--arch <id>`` resolves here.

``REGISTRY`` maps the assignment's architecture ids (and the paper's own
evaluation models) to :class:`repro.configs.base.ModelConfig` instances.
``reduced(cfg)`` derives a CPU-sized config of the same family for smoke
tests (small layers/width/experts/vocab; full configs are only exercised
via the dry-run's ShapeDtypeStructs).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (
    EncoderConfig,
    ModelConfig,
    MoEConfig,
    RGLRUConfig,
    RWKV6Config,
    ShapeSpec,
    SHAPES,
    VisionConfig,
    shape_applicability,
)

from repro.configs.llama4_maverick_400b import CONFIG as _llama4
from repro.configs.deepseek_v2_lite import CONFIG as _dsv2lite
from repro.configs.deepseek_67b import CONFIG as _ds67
from repro.configs.qwen3_32b import CONFIG as _qwen3
from repro.configs.smollm_360m import CONFIG as _smollm
from repro.configs.qwen2_5_14b import CONFIG as _qwen25
from repro.configs.recurrentgemma_2b import CONFIG as _rgemma
from repro.configs.rwkv6_1p6b import CONFIG as _rwkv6
from repro.configs.whisper_base import CONFIG as _whisper
from repro.configs.llama3_2_vision_90b import CONFIG as _llamav
from repro.configs.llama2_7b import CONFIG as _llama2
from repro.configs.opt_6_7b import CONFIG as _opt

# The 10 assigned architectures (dry-run / roofline cells) ...
ASSIGNED = {
    c.name: c
    for c in [
        _llama4, _dsv2lite, _ds67, _qwen3, _smollm,
        _qwen25, _rgemma, _rwkv6, _whisper, _llamav,
    ]
}
# ... plus the paper's own evaluation models (used by the LLMS benchmarks).
REGISTRY = dict(ASSIGNED)
REGISTRY[_llama2.name] = _llama2
REGISTRY[_opt.name] = _opt


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]


def reduced(cfg: ModelConfig) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    kw = dict(
        name=cfg.name + "-reduced",
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=128,
        vocab=512,
        head_dim=16,
        max_seq=256,
    )
    if cfg.family == "moe":
        kw["moe"] = MoEConfig(n_experts=4, top_k=cfg.moe.top_k if cfg.moe.top_k <= 2 else 2,
                              d_expert=96, n_shared=cfg.moe.n_shared,
                              d_shared=96 if cfg.moe.d_shared else 0)
    if cfg.family == "mla_moe":
        # a dense first layer, then dropless top-2 of 8 routed experts,
        # and YaRN at a small factor
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=8, top_k=2, d_expert=96, n_shared=1,
            d_shared=96, first_dense=min(cfg.moe.first_dense, 1),
            d_dense_ff=128)
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
            factor=min(cfg.mla.factor, 4.0),
            original_max_position_embeddings=64)
    if cfg.family == "rglru_hybrid":
        kw["n_layers"] = 5  # rec,rec,attn,rec,rec
        kw["rglru"] = RGLRUConfig(lru_width=64, conv_width=4, window=64,
                                  block_pattern=cfg.rglru.block_pattern)
        kw["head_dim"] = 32
        kw["n_kv_heads"] = 1
    if cfg.family == "rwkv6":
        kw["n_heads"] = 4
        kw["n_kv_heads"] = 4
        kw["rwkv"] = RWKV6Config(head_dim=16, decay_lora=8, mix_lora=4,
                                 chunk_len=16)
    if cfg.family == "encdec":
        kw["encoder"] = EncoderConfig(n_layers=2, n_frames=32)
        kw["n_layers"] = 2
    if cfg.family == "vlm":
        kw["n_layers"] = 10
        kw["vision"] = VisionConfig(n_image_tokens=16, d_vision=48,
                                    cross_attn_every=5)
    return cfg.with_overrides(**kw)


__all__ = [
    "ASSIGNED", "REGISTRY", "SHAPES", "ShapeSpec", "ModelConfig",
    "get_config", "reduced", "shape_applicability",
]
