"""Operation and byte counts against hand counts for both
configurations, and the peak table."""
import json

import pytest

from chipbench import flops
from chipbench.harness import CHECKOUT


def cfg(name):
    return json.loads((CHECKOUT / "chipbench" / "configs"
                       / f"{name}.json").read_text())


def test_smollm_matmul_flops_per_token():
    # 32 layers x 2 x (960*960 + 2*960*320 + 960*960 + 3*960*2560)
    assert flops.dense_matmul_flops_per_token(cfg("smollm-360m")) \
        == 32 * 2 * 9_830_400 == 629_145_600


def test_qwen_matmul_flops_per_token():
    # 4 layers x 2 x (5120^2 + 2*5120*1024 + 5120^2 + 3*5120*13824)
    assert flops.dense_matmul_flops_per_token(cfg("qwen2.5-14b")) \
        == 4 * 2 * 275_251_200 == 2_202_009_600


def test_extend_counts_causal_attention():
    c = cfg("smollm-360m")
    # 4 new tokens after 10: attended 11 + 12 + 13 + 14 = 50 positions,
    # 4 FLOPs per (head, dim, position) per layer
    assert flops.dense_extend_flops(c, 4, 10) \
        == 4 * 629_145_600 + 32 * 4 * 15 * 64 * 50


def test_codec_bytes():
    # (16, 10240) bf16 block <-> 4 packed rows of 2-bit codes + scales
    assert flops.chunk_codec_bytes((16, 10240), 2, True) \
        == 16 * 10240 * 2 + 4 * 10240 + 4 * 10240
    assert flops.chunk_codec_bytes((16, 10240), 8, False) \
        == 16 * 10240 * 2 + 16 * 10240 + 4 * 10240


def test_peaks_known_and_unknown():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
