"""CPU dry run of the decode cell at a small size: it stops at the
window's end with its backlog, reports tokens per second, and its
finished calls agree with the plain reference."""
import pytest

from chipbench.tests import small


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    small.interpret_kernels(monkeypatch)


def test_decode_cell_dry_run():
    res, extra, in_window = small.run("qwen2.5-14b.decode")
    assert res["correct"], res["checks"]
    assert in_window == (0, 0)
    assert set(res["metrics"]) == small.end_to_end("qwen2.5-14b.decode")
    assert "tokens_per_s" in res["metrics"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    w = extra["window"]
    # no drain: what is still queued at the end is cancelled, not served
    assert w["done"] <= w["due"] and w["last_done_after_window_s"] < 5.0
