"""The trace reduction on a small trace recorded on a TPU v5e: two
jitted programs, five times each, one of them inside a host span."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def reduced(monkeypatch_module=None):
    old = tr.SPAN
    tr.SPAN = "bench."            # the span name this trace was made with
    try:
        yield tr.reduce(tr.load(TRACE), window_s=0.060694)
    finally:
        tr.SPAN = old


def test_programs_and_spans(reduced):
    progs = reduced["programs"]
    assert len(progs) == 10 and {p.name for p in progs} == {"jit__lambda"}
    # the matmul ran inside "bench.step", the elementwise program outside
    spans = [p.span for p in progs]
    assert spans.count("bench.step") == 5 and spans.count(None) == 5
    t, n = tr.time_of(progs, r"^jit__lambda$", "step")
    assert n == 5 and 4.5e-4 < t < 5.5e-4


def test_busy_is_the_union_of_programs(reduced):
    assert reduced["busy_s"] == pytest.approx(
        sum(p.dur for p in reduced["programs"]) / 1e9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_idle_gaps_and_ops(reduced):
    idle = sum(v for _, v in reduced["idle_gaps"])
    assert 0.03 < idle <= 0.060694 - reduced["busy_s"]
    names = [k for k, _ in reduced["device_ops"]]
    assert "jit__lambda @ step" in names and "jit__lambda" in names


def test_merged_intervals():
    assert tr.merged([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    spans = [(0.0, 10.0, "bench:a"), (2.0, 4.0, "bench:b")]
    assert tr.innermost(spans, 3.0) == "bench:b"
    assert tr.innermost(spans, 5.0) == "bench:a"
    assert tr.innermost(spans, 11.0) is None
