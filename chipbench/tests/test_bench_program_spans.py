"""Traced CPU dry runs of both cells at a small size: the per-layer
metrics read from the program's own spans and counters
(``router.stats()["telemetry"]``) read a value, and one that falls in
range; without the program's registry they read nothing."""
import pytest

from chipbench import harness
from chipbench.tests import small

NEW = {"smollm-360m.switch": ["router.dispatch_busy_share",
                              "residency.swap_out_ms_per_call",
                              "residency.pages_admitted_per_call"],
       "qwen2.5-14b.decode": ["model.decode_host_ms"]}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    small.interpret_kernels(monkeypatch)


@pytest.mark.parametrize("workload", sorted(NEW))
def test_program_span_metrics_read_in_a_traced_run(workload):
    bench = harness.load_benchmark()
    listed = {m["name"] for m in harness.metrics_for(bench, workload, True)}
    assert set(NEW[workload]) <= listed
    res, _, _ = small.run(workload, trace=True)
    m = res["metrics"]
    for name in NEW[workload]:
        assert name in m, (name, sorted(m))
        assert m[name]["value"] > 0
    if workload == "smollm-360m.switch":
        assert m["router.dispatch_busy_share"]["value"] <= 100
        assert m["router.dispatch_busy_share"]["unit"] == "%"


@pytest.mark.parametrize("name", sorted(n for v in NEW.values()
                                        for n in v))
def test_readers_find_nothing_without_program_telemetry(name):
    obs = {"router": {"decode_rounds": 3, "decoded_tokens": 9},
           "trace_t0": 1.0, "trace_t1": 2.0}
    assert harness.reader(name)(obs) is None
