"""The routed decode cell: the must-read byte counts of a decode round
against hand counts at DeepSeek-V2-Lite's configuration, the reader of
``moe.decode_roofline`` on a made-up trace and counters (and nothing
without the program's counters), and a CPU dry run of
``deepseek-v2-lite.decode`` at a small size."""
import json
from types import SimpleNamespace

import pytest

from chipbench import harness, moe_bytes
from chipbench.harness import CHECKOUT
from chipbench.tests import small

# hidden 256: at 64 a single routing flip between near-tied experts (the
# bf16 program and the f32 reference rounding the router's input apart)
# moves the logits by up to 0.4; at 256, as at the published width (0.06
# at most on the chip), by a few hundredths
SMALL = dict(hidden_size=256, intermediate_size=256, moe_intermediate_size=64,
             num_hidden_layers=3, first_k_dense_replace=1,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
             vocab_size=8192,
             rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 4,
                           "mscale": 0.707, "mscale_all_dim": 0.707,
                           "original_max_position_embeddings": 64,
                           "type": "yarn"})
CELL = "deepseek-v2-lite.decode"


def cfg():
    return json.loads((CHECKOUT / "chipbench" / "configs"
                       / "deepseek-v2-lite.json").read_text())


def test_round_bytes_hand_counts():
    c = cfg()
    # attention a layer: q 2048*16*192 + kv down 2048*576 + kv up
    # 512*16*256 + o 16*128*2048 + norms 2*2048 + 512 = 13,767,168
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 + 4_608
    dense = attn + 3 * 2048 * 10944                    # layer 0
    routed = attn + 3 * 2048 * 2816 + 2048 * 64        # shared + router
    head = 2048 * 102400 + 2048                        # head + final norm
    assert moe_bytes.nonexpert_weight_bytes(c) == 2 * (
        dense + 8 * routed + head) == 1_080_644_608
    assert moe_bytes.expert_bytes(c) == 3 * 2048 * 1408 * 2 == 17_301_504
    assert moe_bytes.page_bytes(c, 16) == 9 * 16 * 576 * 2 == 165_888
    assert moe_bytes.decode_round_bytes(c, 16, 280.0, 1000.0) == (
        1_080_644_608 + 280 * 17_301_504 + 1000 * 165_888)


def _obs(counters, rounds_traced=10, t_round=0.012):
    prog = [SimpleNamespace(name="jit__unknown", span="bench:"
                            "service.decode_round", dur=t_round * 1e9)
            ] * rounds_traced
    return {"trace": {"programs": prog},
            "router": {"telemetry": {"counters": counters}} if counters
            else {}, "cfg": cfg(), "device_kind": "TPU v5 lite",
            "mix": {"service": {"chunk_tokens": 16}}}


def test_roofline_reader():
    read = harness.reader("moe.decode_roofline")
    obs = _obs({"moe.decode_rounds": 100, "moe.experts_hit": 28_000,
                "decode.pages_attended": 100_000})
    need = moe_bytes.decode_round_bytes(cfg(), 16, 280.0, 1000.0)
    assert read(obs) == pytest.approx(100 * need / 819e9 / 0.012)
    assert 0 < read(obs) < 100
    assert read(_obs(None)) is None                   # the parent program
    assert read(_obs({"decode.pages_attended": 5})) is None
    assert read(dict(_obs({"moe.decode_rounds": 1}), trace=None)) is None


@pytest.fixture
def _interpret(monkeypatch):
    small.interpret_kernels(monkeypatch)


def test_decode_cell_dry_run(_interpret):
    res, extra, in_window = small.run(CELL, size=SMALL)
    assert res["correct"], res["checks"]
    assert in_window == (0, 0)
    assert set(res["metrics"]) == small.end_to_end(CELL)
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    w = extra["window"]
    assert w["done"] <= w["due"] and w["last_done_after_window_s"] < 5.0


def test_traced_dry_run_has_no_device_roofline(_interpret):
    """The CPU trace holds no TPU program, so the roofline reader finds
    nothing to divide by and the metric is left out."""
    res, _, _ = small.run(CELL, size=SMALL, trace=True)
    assert "moe.decode_roofline" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
