"""The traffic generator: a fixed number of calls due, the same work in
the same order for every seed, and token ids determined by the seed."""
import numpy as np
import pytest

from chipbench.traffic import generate as gen

MIXES = ["switch", "decode"]
BIG = 2**31 + 12345


@pytest.mark.parametrize("mix", MIXES)
def test_count_is_rate_times_seconds(mix):
    m = gen.load(mix)
    for seconds in (10.0, 51.0):
        s = gen.build(m, seconds, BIG, 1000)
        assert len(s.calls) == round(m["rate_per_s"] * seconds)
        due = [c.due for c in s.calls]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds


@pytest.mark.parametrize("mix", MIXES)
def test_same_work_every_seed(mix):
    """Times, contexts and lengths are the same for every seed, in the
    same order; the seed changes the token ids."""
    m = gen.load(mix)
    a, b = gen.build(m, 51.0, 1, 1000), gen.build(m, 51.0, BIG, 1000)
    key = lambda c: (c.due, c.ctx, len(c.prompt), c.max_new)  # noqa: E731
    assert list(map(key, a.calls)) == list(map(key, b.calls))
    assert [len(h) for h in a.histories] == [len(h) for h in b.histories]
    assert list(map(key, a.warm)) == list(map(key, b.warm))
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a.calls, b.calls))


def test_deterministic_in_seed():
    m = gen.load("switch")
    a, b = gen.build(m, 20.0, BIG, 4096), gen.build(m, 20.0, BIG, 4096)
    assert [c.due for c in a.calls] == [c.due for c in b.calls]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.calls, b.calls))
    assert all(np.array_equal(x, y) for x, y in zip(a.histories,
                                                    b.histories))


@pytest.mark.parametrize("mix", MIXES)
def test_calls_fit_the_half_window(mix):
    """No call may exceed the half window the service admits."""
    m = gen.load(mix)
    half = m["service"]["max_ctx"] // 2
    s = gen.build(m, 51.0, 3, 1000)
    assert max(len(c.prompt) + c.max_new for c in s.calls) <= half
    assert max(len(c.prompt) + c.max_new for c in s.warm) <= half


def test_stratified_lengths():
    assert list(gen.lengths({"dist": "uniform", "lo": 0, "hi": 3}, 4)) \
        == [0, 1, 2, 3]
    ln = gen.lengths({"dist": "lognormal", "median": 32, "sigma": 0.7,
                      "lo": 8, "hi": 128}, 101)
    assert ln[50] == 32 and ln.min() >= 8 and ln.max() <= 128
    t3 = gen.lengths({"dist": "table3", "datasets": ["sst2", "samsum"],
                      "prompt_share": 1.0}, 4)
    assert list(t3) == [32, 78, 150, 250]


def test_warm_calls_cover_every_bucket():
    m = gen.load("switch")
    s = gen.build(m, 51.0, 5, 1000)
    need = {gen._bucket(len(c.prompt), 16) for c in s.calls}
    have = {gen._bucket(len(c.prompt), 16) for c in s.warm}
    assert need <= have
    batch = [c for c in s.warm if c.max_new > 1]
    assert len(batch) == m["service"]["decode_batch"]
    assert len({c.ctx for c in batch}) == len(batch)


def test_contexts_are_balanced_and_shuffled():
    m = gen.load("switch")
    s = gen.build(m, 51.0, BIG, 1000)
    counts = np.bincount([c.ctx for c in s.calls], minlength=m["contexts"])
    assert counts.max() - counts.min() <= 1
    assert [c.ctx for c in s.calls] != sorted(c.ctx for c in s.calls)
