"""Operation and byte counts from shapes, for the per-layer metrics that
divide a count by a device time.  Model FLOPs only: padding, masked
positions and recomputation are not counted."""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _dims(cfg: dict):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return (cfg["num_hidden_layers"], d, H, cfg["num_key_value_heads"], hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def dense_matmul_flops_per_token(cfg: dict) -> int:
    """Projections and MLP of every layer for one token (no LM head)."""
    L, d, H, KV, hd, ff, _ = _dims(cfg)
    per_layer = 2 * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff)
    return L * per_layer


def dense_extend_flops(cfg: dict, n_new: int, n0: int) -> int:
    """Prefill of ``n_new`` tokens appended at positions [n0, n0+n_new)
    of a context: projections and MLP for every new token, plus causal
    attention of each new token over itself and everything before it
    (QK^T and PV, 2 FLOPs a multiply-add)."""
    L, d, H, KV, hd, ff, _ = _dims(cfg)
    # sum over p in [n0, n0 + n_new) of (p + 1) attended positions
    attended = n_new * n0 + n_new * (n_new + 1) // 2
    return (n_new * dense_matmul_flops_per_token(cfg)
            + L * 4 * H * hd * attended)


def chunk_codec_bytes(shape, bits: int, quantize: bool) -> int:
    """HBM bytes one chunk codec call moves: a (T, F) bf16 block on one
    side, T*bits/8 packed int8 rows plus F float32 scales on the other."""
    T, F = shape
    raw = T * F * 2
    packed = (T * bits // 8) * F + 4 * F
    return raw + packed


def chunk_codec_flops(shape, bits: int, quantize: bool) -> int:
    """Elementwise work of one codec call: a handful of VPU operations
    an element (abs-max, divide, round, clip, shift/or); far below the
    byte bound, counted so the roofline takes the larger of the two."""
    T, F = shape
    return T * F * (6 if quantize else 3)
