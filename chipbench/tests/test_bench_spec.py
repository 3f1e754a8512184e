"""BENCHMARK.json loads, meets the benchmark's rules, and every name in
it resolves to a file under chipbench/."""
import copy
import json

from chipbench import validate
from chipbench.harness import CHECKOUT, metrics_for


def bench():
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def test_benchmark_is_valid():
    assert validate.problems(bench()) == []


def test_validator_finds_faults():
    b = bench()
    bad = copy.deepcopy(b)
    bad["per_layer"][0]["moves"] = "nonexistent"
    assert validate.problems(bad)
    bad = copy.deepcopy(b)
    bad["end_to_end"][0]["unit"] = "milli seconds"
    assert validate.problems(bad)
    bad = copy.deepcopy(b)
    del bad["per_layer"][0]["workloads"]
    assert validate.problems(bad)
    bad = copy.deepcopy(b)
    bad["end_to_end"][0]["bound"] = 0.3
    assert validate.problems(bad)


def test_each_cell_reports_what_its_layers_move():
    b = bench()
    for w in b["workloads"]:
        e2e = {m["name"] for m in metrics_for(b, w["name"], False)}
        per = metrics_for(b, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and per
        assert all(m["moves"] in e2e for m in per)


def test_tokens_per_s_only_above_the_knee():
    b = bench()
    tps = next(m for m in b["end_to_end"] if m["name"] == "tokens_per_s")
    for w in b["workloads"]:
        mix = json.loads((CHECKOUT / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        # a cell with no drain runs above the knee
        assert (w["name"] in tps["workloads"]) == (mix["drain_s"] == 0)
