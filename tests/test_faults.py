"""Fault injection + recovery (DESIGN.md §6): seeded failpoint
registry, checksummed chunk/blob envelopes, retry classification,
recompute recovery token-identity, ENOSPC degraded mode, watchdog
preemption, and degraded background shedding."""
import os
import tempfile
import threading

import numpy as np
import pytest

from conftest import tiny_model
from repro.core.faults import (FAULTS, ChunkCorruptError, DiskFullError,
                               FaultRegistry, FaultSpec,
                               PersistentIOError, SwapTimeoutError,
                               TransientIOError, canon_key, clear_faults,
                               corrupt_file, install_faults,
                               plan_from_config, retryable, set_disk_full,
                               with_retries)
from repro.core.pagepool import PagePool
from repro.core.requests import BACKGROUND, FOREGROUND
from repro.core.restore import (read_chunk_file, verify_chunk_file,
                                write_chunk_file)
from repro.core.scheduler import ServiceRouter
from repro.core.service import LLMSConfig, LLMService
from repro.core.swap import AsyncSwapper, DiskStore, open_blob, seal_blob


@pytest.fixture(autouse=True)
def _clean_faults():
    clear_faults()
    yield
    clear_faults()


def _check_outcomes(reg, site, key, n, exc):
    """Outcome vector of ``n`` consecutive checks (True = raised)."""
    out = []
    for _ in range(n):
        try:
            reg.check(site, key)
            out.append(False)
        except exc:
            out.append(True)
    return out


def _find_seed(spec, site, key, want, exc, limit=5000):
    """A seed whose first len(want) draws produce exactly ``want``."""
    reg = FaultRegistry()
    for seed in range(limit):
        reg.install([spec], seed)
        if _check_outcomes(reg, site, key, len(want), exc) == want:
            return seed
    raise AssertionError("no seed found for wanted outcome pattern")


# --------------------------------------------------------------------- #
# registry units
# --------------------------------------------------------------------- #
def test_canon_key():
    assert canon_key((3, 7)) == "3:7"
    assert canon_key("/tmp/x/ctx3_chunk7.pkl") == "ctx3_chunk7.pkl"
    assert canon_key("/tmp/x/ctx3_chunk7.pkl.tmp") == "ctx3_chunk7.pkl"


def test_transient_fires_consecutively_then_heals():
    spec = FaultSpec(kind="transient_eio", sites=("disk.read",),
                     rate=0.3, fail_n=2)
    want = [True, True, False, False, False, False]
    seed = _find_seed(spec, "disk.read", (1, 0), want, TransientIOError)
    reg = FaultRegistry()
    reg.install([spec], seed)
    assert _check_outcomes(reg, "disk.read", (1, 0), 6,
                           TransientIOError) == want
    assert reg.counters()["injected"]["transient_eio"] == 2


def test_same_seed_replays_identically():
    spec = FaultSpec(kind="transient_eio", sites=("disk.read",
                                                  "disk.write"), rate=0.4)
    reg = FaultRegistry()
    runs = []
    for _ in range(2):
        reg.install([spec], 99)
        out = []
        for key in [(0, 0), (0, 1), (1, 0)] * 4:
            out += _check_outcomes(reg, "disk.read", key, 2,
                                   TransientIOError)
            out += _check_outcomes(reg, "disk.write", key, 2,
                                   TransientIOError)
        runs.append(out)
    assert runs[0] == runs[1]
    assert any(runs[0])          # rate 0.4 over 48 draws: some fire
    reg.install([spec], 100)     # different seed -> different draws
    out2 = []
    for key in [(0, 0), (0, 1), (1, 0)] * 4:
        out2 += _check_outcomes(reg, "disk.read", key, 2,
                                TransientIOError)
        out2 += _check_outcomes(reg, "disk.write", key, 2,
                                TransientIOError)
    assert out2 != runs[0]


def test_persistent_marks_key_until_rewrite():
    spec = FaultSpec(kind="persistent_eio", sites=("disk.write",),
                     rate=0.3)
    # first draw fires; the mark (not fresh draws) keeps it failing
    want = [True, True, True, True]
    seed = _find_seed(spec, "disk.write", (2, 0), want, PersistentIOError)
    reg = FaultRegistry()
    reg.install([spec], seed)
    assert _check_outcomes(reg, "disk.write", (2, 0), 4,
                           PersistentIOError) == want
    reg.note_write_ok((2, 0))
    # mark cleared; the seed search guaranteed ops 1..3 drew clean, but
    # op 4+ is a fresh draw — just assert the mark itself is gone
    assert canon_key((2, 0)) not in reg._persistent


def test_enospc_and_disk_full_window():
    reg = FaultRegistry()
    reg.install([FaultSpec(kind="enospc", sites=("disk.write",),
                           rate=1.0)], 0)
    with pytest.raises(DiskFullError):
        reg.check("disk.write", (0, 0))
    reg.check("disk.read", (0, 0))       # read sites unaffected
    reg.clear()
    assert not reg.active
    reg.set_disk_full(True)
    assert reg.active and reg.disk_full
    with pytest.raises(DiskFullError):
        reg.check("disk.write", (0, 0))
    reg.check("disk.read", (0, 0))
    reg.set_disk_full(False)
    reg.check("disk.write", (0, 0))


def test_corrupt_action_and_corrupt_file():
    reg = FaultRegistry()
    reg.install([FaultSpec(kind="torn_write", sites=("disk.write",),
                           rate=1.0)], 0)
    assert reg.corrupt_action((0, 0)) == "torn"
    reg.install([FaultSpec(kind="bit_flip", sites=("disk.write",),
                           rate=1.0)], 0)
    assert reg.corrupt_action((0, 0)) == "bit_flip"
    reg.clear()
    assert reg.corrupt_action((0, 0)) is None

    tmp = tempfile.mkdtemp()
    p = os.path.join(tmp, "f.bin")
    raw = bytes(range(256)) * 4
    with open(p, "wb") as f:
        f.write(raw)
    corrupt_file(p, "torn")
    assert os.path.getsize(p) == len(raw) // 2
    with open(p, "wb") as f:
        f.write(raw)
    corrupt_file(p, "bit_flip")
    with open(p, "rb") as f:
        got = f.read()
    assert len(got) == len(raw) and got != raw
    assert sum(a != b for a, b in zip(got, raw)) == 1


def test_plan_from_config_validation():
    specs, seed = plan_from_config(
        {"transient_eio": 0.1, "bit_flip": 0.02, "seed": 42}, 7)
    assert seed == 42
    assert {s.kind for s in specs} == {"transient_eio", "bit_flip"}
    specs, seed = plan_from_config({"enospc": 0.5}, 7)
    assert seed == 7 and specs[0].sites == ("disk.write",)
    with pytest.raises(ValueError):
        plan_from_config({"nope": 1.0}, 0)


# --------------------------------------------------------------------- #
# checksummed envelopes
# --------------------------------------------------------------------- #
def test_blob_envelope_detects_tampering():
    blob = b"payload bytes" * 20
    raw = seal_blob(blob)
    assert open_blob(raw, "t") == blob
    flipped = bytearray(raw)
    flipped[len(raw) // 2] ^= 0x10
    with pytest.raises(ChunkCorruptError):
        open_blob(bytes(flipped), "t")
    with pytest.raises(ChunkCorruptError):
        open_blob(raw[:len(raw) // 2], "t")
    with pytest.raises(ChunkCorruptError):
        open_blob(b"XXXX" + raw[4:], "t")


def _mk_chunk_file(path):
    from repro.core.chunks import CompressedChunk
    x = np.random.RandomState(0).randn(16, 128).astype(np.float16)
    cc = CompressedChunk(
        bits=16, n_tokens=16,
        data={"k": (x, np.zeros(0, np.float32)),
              "v": (x * 2, np.zeros(0, np.float32))},
        shapes={"k": (16, 128), "v": (16, 128)})
    write_chunk_file(path, cc, n_layers=4)
    return cc


@pytest.mark.parametrize("action", ["torn", "bit_flip"])
def test_chunk_file_detects_corruption(action):
    tmp = tempfile.mkdtemp()
    p = os.path.join(tmp, "c.bin")
    _mk_chunk_file(p)
    verify_chunk_file(p)                 # intact: no raise
    corrupt_file(p, action)
    with pytest.raises(ChunkCorruptError):
        read_chunk_file(p)
    if action == "torn":                 # structural pre-validation
        with pytest.raises(ChunkCorruptError):
            verify_chunk_file(p)


def test_tmp_sweep_regression():
    """A crash between temp-write and os.replace leaves an orphan
    ``*.tmp``; startup must sweep it and never serve its bytes."""
    root = tempfile.mkdtemp()
    store = DiskStore(root)
    store.write((0, 0), {"x": 1})
    orphan = store._path((0, 1)) + ".tmp"
    with open(orphan, "wb") as f:
        f.write(b"garbage from a torn writer")
    store2 = DiskStore(root)             # restart
    assert store2.tmp_swept == 1
    assert not os.path.exists(orphan)
    assert store2.read((0, 0)) == {"x": 1}


# --------------------------------------------------------------------- #
# retry classification + swapper behaviour
# --------------------------------------------------------------------- #
def test_retryable_classification():
    assert retryable(TransientIOError("x"))
    assert retryable(PersistentIOError("x"))     # exhausts the budget
    assert not retryable(DiskFullError("x"))     # retry can't free space
    assert not retryable(ChunkCorruptError("x"))
    assert not retryable(FileNotFoundError("x"))
    assert not retryable(ValueError("x"))


def test_with_retries_bounded_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientIOError("x")
        return "ok"
    assert with_retries(flaky, attempts=3, base_s=0.0) == "ok"
    assert calls["n"] == 3

    calls["n"] = 0

    def hard():
        calls["n"] += 1
        raise ChunkCorruptError("x")
    with pytest.raises(ChunkCorruptError):
        with_retries(hard, attempts=3, base_s=0.0)
    assert calls["n"] == 1               # non-retryable: no second try


def test_swapper_retries_transient_read():
    spec = FaultSpec(kind="transient_eio", sites=("disk.read",),
                     rate=0.3, fail_n=1)
    want = [True, False, False, False]
    seed = _find_seed(spec, "disk.read", (0, 0), want, TransientIOError)
    store = DiskStore(tempfile.mkdtemp())
    store.write((0, 0), {"x": 5})
    install_faults([spec], seed)
    sw = AsyncSwapper(store, retries=3, retry_base_s=0.0)
    try:
        assert sw.read((0, 0)) == {"x": 5}
        assert sw.io_retries == 1 and sw.io_recovered == 1
    finally:
        clear_faults()
        sw.shutdown()


def test_wait_flush_timeout_and_shutdown_cancels_chained():
    store = DiskStore(tempfile.mkdtemp())
    sw = AsyncSwapper(store, workers=1)
    gate = threading.Event()
    f1 = sw.submit((0, 0), lambda: gate.wait(10))
    f2 = sw.submit((0, 0), lambda: 2)    # chained behind the wedged f1
    try:
        with pytest.raises(SwapTimeoutError):
            sw.wait((0, 0), timeout=0.05)
        with pytest.raises(SwapTimeoutError):
            sw.flush(timeout=0.05)
        sw.shutdown(timeout=0.1)         # must not hang on the wedge
        assert f2.cancelled()            # never started -> cancelled
        assert not f1.cancelled()        # in flight: left to finish
    finally:
        gate.set()


def test_pool_admit_failpoint_retries_in_place():
    spec = FaultSpec(kind="transient_eio", sites=("pool.admit",),
                     rate=0.3, fail_n=1)
    want = [True, False, False]
    seed = _find_seed(spec, "pool.admit", (5, 0), want, TransientIOError)
    install_faults([spec], seed)
    pp = object.__new__(PagePool)
    pp.admit_fault_retries = 0
    pp._admit_check(5, 0)                # transient: retried on the spot
    assert pp.admit_fault_retries == 1


# --------------------------------------------------------------------- #
# spec plumbing
# --------------------------------------------------------------------- #
def test_scenario_spec_fault_validation():
    from repro.loadgen.spec import ScenarioSpec, validate_spec
    ok = ScenarioSpec(name="t", n_contexts=1, n_calls=1,
                      faults={"transient_eio": 0.1,
                              "disk_full_windows": [[1.0, 2.0]],
                              "swap_deadline_s": 5.0})
    validate_spec(ok)
    with pytest.raises(ValueError):
        validate_spec(ok.override(faults={"bogus_knob": 1.0}))
    with pytest.raises(ValueError):
        validate_spec(ok.override(faults={"disk_full_windows": [[5, 2]]}))
    with pytest.raises(ValueError):
        validate_spec(ok.override(faults={"swap_deadline_s": 0}))


def test_config_plumbs_watchdog_and_retries():
    cfg, model, params = tiny_model("smollm-360m")
    sc = LLMSConfig(policy="llms_nocomp", max_ctx_len=64, chunk_tokens=16,
                    memory_budget=100_000, io_retries=5,
                    io_retry_base_s=0.001, swap_deadline_s=7.5,
                    swap_dir=tempfile.mkdtemp())
    svc = LLMService(model, params, sc)
    try:
        assert svc.swapper.retries == 5
        assert svc.res._deadline == 7.5
        assert "degraded_mode" in svc.stats()
        assert "chunks_recovered_recompute" in svc.stats()
    finally:
        svc.close()


# --------------------------------------------------------------------- #
# end-to-end recovery
# --------------------------------------------------------------------- #
def _svc(policy="llms_nocomp", budget=12_000, paged=False, **kw):
    cfg, model, params = tiny_model("smollm-360m")
    sc = LLMSConfig(policy=policy, max_ctx_len=128, chunk_tokens=16,
                    memory_budget=budget, paged_pool=paged,
                    swap_dir=tempfile.mkdtemp(), **kw)
    return LLMService(model, params, sc), cfg


def _drive(svc, cfg, n_ctx=3, rounds=9, seed=7, max_new=4):
    rng = np.random.RandomState(seed)
    stubs = [svc.newLLMCtx() for _ in range(n_ctx)]
    outs = []
    for r in range(rounds):
        prompt = rng.randint(1, cfg.vocab, size=12).tolist()
        _, gen = svc.callLLM(stubs[r % n_ctx], prompt,
                             max_new_tokens=max_new)
        outs.append(gen)
    return outs


@pytest.mark.parametrize("paged", [False, True])
def test_corrupt_chunk_recovery_token_identity(paged):
    """Bit-flipped chunk files are detected by CRC and recovered by
    recompute from tokens; under the 16-bit policy the recovered run's
    tokens are IDENTICAL to the fault-free run's (DESIGN.md §6)."""
    svc, cfg = _svc(paged=paged)
    clean = _drive(svc, cfg)
    svc.close()

    install_faults(
        [FaultSpec(kind="bit_flip", sites=("disk.write",), rate=0.25)],
        seed=2024)
    svc2, _ = _svc(paged=paged)
    try:
        faulty = _drive(svc2, cfg)
        st = svc2.stats()
    finally:
        clear_faults()
        svc2.close()
    assert st["faults_injected_total"] > 0, "no faults drawn: dead test"
    assert st["chunks_corrupt_detected"] > 0
    assert st["chunks_recovered_recompute"] > 0
    assert st["recover_failed"] == 0
    assert faulty == clean


def test_transient_eio_recovered_by_retries():
    install_faults(
        [FaultSpec(kind="transient_eio",
                   sites=("disk.read", "disk.write", "swap.worker"),
                   rate=0.10, fail_n=1)], seed=77)
    svc, cfg = _svc()
    try:
        _drive(svc, cfg)
        st = svc.stats()
    finally:
        clear_faults()
        svc.close()
    assert st["faults_injected_total"] > 0
    assert st["io_retries"] > 0
    assert st["io_failed_jobs"] == 0     # fail_n=1 always heals in-budget
    assert st["recover_failed"] == 0


def test_pipelined_restore_failure_propagates():
    """Only a storage fault the restore feed recorded falls back to
    whole-file reads: any other failure of the pipelined scan (a compile
    or runtime error on the device) fails the call instead of being
    counted as a storage fault."""
    svc, cfg = _svc(policy="llms")

    def broken(*a, **kw):
        raise RuntimeError("scan failed")
    svc.exe.run_pipelined = broken
    try:
        with pytest.raises(RuntimeError, match="scan failed"):
            _drive(svc, cfg)
        st = svc.stats()
    finally:
        svc.close()
    assert st["chunks_corrupt_detected"] == st["io_errors_detected"] == 0
    assert st["pipelined_restores"] == 0


def test_enospc_degraded_cycle_token_identity():
    """Disk-full window: degraded mode is entered (AoT off, evictions
    drop dirty payloads), foreground calls keep completing via
    recompute, and the probe write exits the mode once space returns."""
    svc, cfg = _svc()
    clean = _drive(svc, cfg, rounds=12)
    svc.close()

    svc3, _ = _svc()
    try:
        rng = np.random.RandomState(7)
        stubs = [svc3.newLLMCtx() for _ in range(3)]
        outs = []
        for r in range(12):
            if r == 4:
                set_disk_full(True)
            if r == 8:
                set_disk_full(False)
            prompt = rng.randint(1, cfg.vocab, size=12).tolist()
            _, gen = svc3.callLLM(stubs[r % 3], prompt, max_new_tokens=4)
            outs.append(gen)
            if r == 6:
                assert svc3.res.degraded, \
                    "writes failing but degraded mode never entered"
        st = svc3.stats()
    finally:
        clear_faults()
        svc3.close()
    assert st["degraded_entries"] >= 1
    assert st["degraded_exits"] >= 1
    assert not st["degraded_mode"], "probe never exited degraded mode"
    assert outs == clean
    # post-exit flush: nothing left permanently dirty
    assert st["recover_failed"] == 0


# --------------------------------------------------------------------- #
# router: degraded shedding + watchdog preemption
# --------------------------------------------------------------------- #
def test_degraded_sheds_background_until_fg_served():
    svc, cfg = _svc(budget=200_000)
    router = ServiceRouter(svc, predict=False, start=False, slice_steps=2)
    try:
        fg = router.register_app("fg", "foreground")
        bg = router.register_app("bg", "background")
        sf, sb = fg.new_ctx(), bg.new_ctx()
        st_bg = bg.stream(sb, [1, 2, 3], max_new_tokens=2)
        st_fg = fg.stream(sf, [4, 5, 6], max_new_tokens=2)
        svc.res._enter_degraded()
        jobs = router._pop_batch(4, set())
        assert [j["prio"] for j in jobs] == [FOREGROUND]
        assert router.bg_shed == 1
        router._run_batch(jobs, refill=False)
        # only background remains: it must NOT be shed (livelock guard)
        jobs2 = router._pop_batch(4, set())
        assert [j["prio"] for j in jobs2] == [BACKGROUND]
        router._run_batch(jobs2, refill=False)
        assert st_fg.done and st_bg.done
        assert st_fg.error is None and st_bg.error is None
        assert router.stats()["bg_shed"] == 1
    finally:
        router.shutdown()
        clear_faults()
        svc.close()


def test_watchdog_timeout_requeues_then_fails():
    svc, cfg = _svc(budget=200_000)
    router = ServiceRouter(svc, predict=False, start=False)
    try:
        app = router.register_app("a", "foreground")
        stub = app.new_ctx()
        real = svc.begin_call
        calls = {"n": 0}

        def wedged_twice(stub_, req):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise SwapTimeoutError("swap wedged")
            return real(stub_, req)

        svc.begin_call = wedged_twice
        s1 = app.stream(stub, [1, 2, 3], max_new_tokens=2)
        router.drain()
        assert s1.done and s1.error is None     # requeued, then served
        assert router.watchdog_preempts == 2

        svc.begin_call = lambda *_: (_ for _ in ()).throw(
            SwapTimeoutError("permanently wedged"))
        s2 = app.stream(stub, [1, 2, 3], max_new_tokens=2)
        router.drain()
        assert isinstance(s2.error, SwapTimeoutError)   # bounded: fails
        assert router.watchdog_preempts == 5            # 2 + 3 more
        svc.begin_call = real
    finally:
        router.shutdown()
        svc.close()
