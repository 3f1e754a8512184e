"""CPU dry runs at a small size, steered from the test: the whole run
(set-up, window, drain, readers, comparison with the plain reference),
then the same with the timed path broken underneath, or with the
lower-precision control in the program's place, which has to come out
not correct."""
import pytest

from chipbench.tests import small

CELLS = ["smollm-360m.switch", "qwen2.5-14b.decode"]


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    small.interpret_kernels(monkeypatch)


def test_dry_run_is_correct():
    res, extra, in_window = small.run("smollm-360m.switch")
    assert res["correct"], res["checks"]
    assert in_window == (0, 0)                  # nothing compiled inside
    assert res["failed"] == 0 and res["attempted"] == 10
    assert set(res["metrics"]) == small.end_to_end("smollm-360m.switch")
    assert list(res)[-1] == "checks"
    assert extra["window"]["done"] == res["attempted"]


def test_closed_contexts(monkeypatch):
    """A call to a context with a call in flight falls due when that call
    completes, and no two calls of one context overlap."""
    from chipbench import harness

    cells = []
    orig = harness.Cell.close

    def close(self):
        cells.append(self)
        orig(self)
    monkeypatch.setattr(harness.Cell, "close", close)
    small.run("smollm-360m.switch", seconds=4.0)
    calls = cells[0].window_calls
    assert len(calls) == 8
    by_ctx = {}
    for s in calls:
        by_ctx.setdefault(s.ctx, []).append(s)
    for seq in by_ctx.values():
        assert seq[0].due == seq[0].orig_due
        for a, b in zip(seq, seq[1:]):
            assert b.due == max(b.orig_due, a.t_done)
            assert b.t_submit >= a.t_done
    assert all(s.t_submit - s.due < 0.05 for s in calls)


def test_traced_run_reads_host_metrics():
    res, _, _ = small.run("qwen2.5-14b.decode", trace=True)
    m = res["metrics"]
    assert 0 < m["router.batch_occupancy"]["value"] <= 100
    # the CPU trace has no TPU programs: device readers find nothing
    assert "model.decode_step_ms" not in m
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", CELLS)
def test_altered_token_is_not_correct(monkeypatch, workload):
    """A token altered where the service produces it: after every third
    decode round, each generation's next token is replaced by its
    successor id."""
    from repro.core.service import LLMService

    orig = LLMService._decode_round_paged
    n = [0]

    def decode_round(self, live, fed):
        orig(self, live, fed)
        n[0] += 1
        if n[0] % 3 == 0:
            for st in live:
                if st.next_tok is not None:
                    st.next_tok = (st.next_tok + 1) % self.model.cfg.vocab
    monkeypatch.setattr(LLMService, "_decode_round_paged", decode_round)
    res, _, _ = small.run(workload)
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_unchanged_state_is_not_correct(monkeypatch, workload):
    """The decode step returns the KV arenas it was given: no new key or
    value row is ever written by decode."""
    from repro.core.executor import ModelExecutor

    orig = ModelExecutor.paged_decode

    def paged_decode(self, arenas, *a, **k):
        _, logits, mass = orig(self, arenas, *a, **k)
        return arenas, logits, mass
    monkeypatch.setattr(ModelExecutor, "paged_decode", paged_decode)
    res, _, _ = small.run(workload)
    assert not res["correct"]


@pytest.mark.parametrize("workload,control,size", [
    ("smollm-360m.switch", "fp8", small.SMALL),
    ("qwen2.5-14b.decode", "int8", small.WIDE)])
def test_lower_precision_control_is_not_correct(workload, control, size):
    """The cell's control: the plain reference with its weights in the
    precision below bf16 in the program's place, judged by the limits
    that judge the program, comes out not correct, where the program
    itself comes out correct."""
    res, extra, _ = small.run(workload, controls=(control,), size=size)
    assert res["correct"], res["checks"]
    assert extra[f"control_{control}"]["correct"] is False
