"""The span-and-counter registry (``core/telemetry.py``): nesting and
self time, per-thread stacks, reset through the router, the snapshot's
shape; on a small paged service, the spans and counters against what
the served path really did; and, under the JAX profiler, the ``llms:``
spans on the host plane of the trace with every program dispatched
inside an executor-entry span."""
import glob
import sys
import tempfile
import threading

import jax
import numpy as np
import pytest

from conftest import tiny_model
from repro.core import telemetry as telemetry_mod
from repro.core.scheduler import ServiceRouter
from repro.core.service import LLMSConfig, LLMService
from repro.core.telemetry import Telemetry


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(telemetry_mod, "time", c)
    return c


def test_nesting_and_self_time(clock):
    tel = Telemetry()
    with tel.span("outer", ctx=7):
        clock.t += 1.0
        with tel.span("inner"):
            clock.t += 2.0
            with tel.span("leaf"):
                clock.t += 0.5
        clock.t += 0.25
        with tel.span("inner"):
            clock.t += 3.0
    sp = tel.snapshot()["spans"]
    assert sp["outer"] == {"count": 1, "total_s": 6.75, "self_s": 1.25,
                           "max_s": 6.75}
    assert sp["inner"] == {"count": 2, "total_s": 5.5, "self_s": 5.0,
                           "max_s": 3.0}
    assert sp["leaf"]["self_s"] == sp["leaf"]["total_s"] == 0.5


def test_span_open_across_reset_counts_from_the_reset(clock):
    tel = Telemetry()
    with tel.span("outer"):
        with tel.span("before"):
            clock.t += 4.0
        clock.t += 1.0
        tel.reset()
        clock.t += 2.0
        with tel.span("after"):
            clock.t += 1.0
    snap = tel.snapshot()
    assert set(snap["spans"]) == {"outer", "after"}
    assert snap["spans"]["outer"]["total_s"] == 3.0
    assert snap["spans"]["outer"]["self_s"] == 2.0
    assert snap["elapsed_s"] == 3.0


def test_counters_and_snapshot_shape(clock):
    tel = Telemetry()
    tel.count("pages_zeroed")
    tel.count("pages_zeroed", 3)
    with tel.span("a"):
        clock.t += 0.5
    snap = tel.snapshot()
    assert set(snap) == {"elapsed_s", "spans", "counters"}
    assert snap["counters"] == {"pages_zeroed": 4}
    assert tel.counter("pages_zeroed") == 4 and tel.counter("none") == 0
    assert set(snap["spans"]["a"]) == {"count", "total_s", "self_s",
                                       "max_s"}
    assert snap["elapsed_s"] == 0.5
    tel.reset()
    assert tel.snapshot() == {"elapsed_s": 0.0, "spans": {},
                              "counters": {}}


def test_threads_keep_their_own_stacks():
    """Two threads with overlapping spans: neither's span is taken for
    the other's child, so each self time stays its own."""
    tel = Telemetry()
    both_open = threading.Barrier(2, timeout=10)
    inner_done = threading.Event()

    def a():
        with tel.span("a"):
            both_open.wait()
            with tel.span("a.inner"):
                threading.Event().wait(0.05)
            inner_done.set()

    def b():
        with tel.span("b"):
            both_open.wait()
            inner_done.wait(10)

    ts = [threading.Thread(target=f) for f in (a, b)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert not any(t.is_alive() for t in ts)
    sp = tel.snapshot()["spans"]
    assert sp["b"]["self_s"] == sp["b"]["total_s"] >= 0.05
    assert sp["a"]["self_s"] == pytest.approx(
        sp["a"]["total_s"] - sp["a.inner"]["total_s"])


def test_concurrent_recording_loses_nothing():
    """More threads than cores, a short switch interval: every span and
    count recorded from every thread is in the totals."""
    tel = Telemetry()
    n_threads, n_each = 16, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_each):
                with tel.span("outer"):
                    with tel.span("inner"):
                        tel.count("n")
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    snap = tel.snapshot()
    assert snap["counters"]["n"] == n_threads * n_each
    assert snap["spans"]["outer"]["count"] == n_threads * n_each
    assert snap["spans"]["inner"]["count"] == n_threads * n_each
    assert snap["spans"]["outer"]["self_s"] <= (
        snap["spans"]["outer"]["total_s"] + 1e-9)


# --------------------------------------------------------------------- #
# the served path on a small paged service
# --------------------------------------------------------------------- #
def make_served(budget=8_000):
    cfg, model, params = tiny_model("smollm-360m")
    sc = LLMSConfig(policy="llms", max_ctx_len=128, chunk_tokens=16,
                    memory_budget=budget, decode_batch=2,
                    swap_dir=tempfile.mkdtemp())
    svc = LLMService(model, params, sc)
    return cfg, svc, ServiceRouter(svc, predict=True, slice_steps=2)


def serve(cfg, router, n_ctx=4, calls=3, seed=3):
    """Calls on ``n_ctx`` contexts in turns, each context several times:
    every later call switches its context back in."""
    rng = np.random.RandomState(seed)
    app = router.register_app("app", "fg")
    stubs = [app.new_ctx() for _ in range(n_ctx)]
    streams = []
    for _ in range(calls):
        for stub in stubs:
            streams.append(app.stream(
                stub, rng.randint(1, cfg.vocab, 10).tolist(),
                max_new_tokens=5))
        router.drain()
    return [s.result() for s in streams]


class SpanNesting:
    """Records, for every ``exe.*`` span entered, the names of the
    spans open on its thread at that moment."""

    def __init__(self, monkeypatch):
        self.tls = threading.local()
        self.exe_parents = []
        enter, exit_ = telemetry_mod._Span.__enter__, \
            telemetry_mod._Span.__exit__
        rec = self

        def _enter(sp):
            st = rec.tls.__dict__.setdefault("stack", [])
            if sp.name.startswith("exe."):
                rec.exe_parents.append((sp.name, list(st)))
            st.append(sp.name)
            return enter(sp)

        def _exit(sp, *exc):
            rec.tls.stack.pop()
            return exit_(sp, *exc)
        monkeypatch.setattr(telemetry_mod._Span, "__enter__", _enter)
        monkeypatch.setattr(telemetry_mod._Span, "__exit__", _exit)


def test_served_path_spans_and_counters(monkeypatch):
    nesting = SpanNesting(monkeypatch)
    cfg, svc, router = make_served()
    exe = svc.exe
    seen = {"admit16": 0, "admit8": 0}
    for name in seen:
        fn = getattr(exe, name + "_fn")

        def counted(*a, _fn=fn, _name=name):
            seen[_name] += 1
            return _fn(*a)
        setattr(exe, name + "_fn", counted)
    with svc:
        serve(cfg, router)
        st = router.stats()
        svc_st = svc.stats()
    tel = st["telemetry"]
    spans, counters = tel["spans"], tel["counters"]
    assert spans["router.round"]["count"] == st["decode_rounds"] > 0
    assert spans["service.decode_round"]["count"] == st["decode_rounds"]
    assert seen["admit16"] > 0
    assert (counters.get("pages_admitted16", 0)
            + counters.get("pages_admitted8", 0)
            == seen["admit16"] + seen["admit8"])
    # the pool reports the same count, read from the registry
    assert svc_st["pool_page_faults"] == seen["admit16"] + seen["admit8"]
    assert counters["calls_finished"] == 12
    assert spans["router.complete"]["count"] == 12
    assert spans["residency.swap_out"]["count"] >= 12
    # the budget forces swap-out to disk and reads back at switch-in
    assert counters["chunks_read"] > 0 and spans["switch_in.read"]["count"]
    assert sum(v for k, v in counters.items()
               if k.startswith("chunks_encoded.")) > 0
    for name in ("service.begin_call", "service.prefill",
                 "residency.switch_in", "switch_in.admit", "decode.prepare",
                 "decode.step", "decode.sample", "decode.density",
                 "exe.paged_extend", "exe.paged_decode", "exe.zero16",
                 "codec.quantize", "store.write", "store.read"):
        assert spans[name]["count"] > 0, name
    for s in spans.values():
        assert 0.0 <= s["self_s"] <= s["total_s"] + 1e-9
        assert s["max_s"] <= s["total_s"] + 1e-9
    # every executor entry runs inside a service or router span
    assert nesting.exe_parents
    for name, parents in nesting.exe_parents:
        assert any(p.startswith(("service.", "router.")) for p in parents), \
            (name, parents)


def make_served_hd128(monkeypatch, in_place, budget=10_000_000):
    """A small paged service of a 2-layer qwen2.5-14b at ``hd`` 128, with
    the rule's backend check steered to ``in_place`` (the kernel
    interpreted where it is taken)."""
    import collections
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.core import executor as executor_mod
    from repro.models import common
    from repro.models.registry import build_model

    monkeypatch.setattr(common, "on_tpu", lambda: in_place)
    # the jitted programs are cached by model, not by the rule's answer
    monkeypatch.setattr(executor_mod, "_JIT_CACHE",
                        collections.OrderedDict())
    cfg = dataclasses.replace(reduced(get_config("qwen2.5-14b")),
                              n_layers=2, head_dim=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sc = LLMSConfig(policy="llms", max_ctx_len=128, chunk_tokens=16,
                    memory_budget=budget, decode_batch=2,
                    swap_dir=tempfile.mkdtemp())
    svc = LLMService(model, params, sc)
    return cfg, svc, ServiceRouter(svc, predict=True, slice_steps=2)


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel", "gather"])
def test_in_place_decode_counters(in_place, monkeypatch):
    """``decode.inplace_rounds`` counts what the rule chose: on the
    kernel's path (the rule's backend check steered, the kernel
    interpreted) every round, on the gather path nothing.
    ``decode.pages_attended`` counts the pages the rows' attention spans
    on either path."""
    cfg, svc, router = make_served_hd128(monkeypatch, in_place)
    exe = svc.exe
    rounds = []
    decode = exe.paged_decode

    def recorded(arenas, toks, pos, *a):
        rounds.append(list(pos))
        return decode(arenas, toks, pos, *a)
    monkeypatch.setattr(exe, "paged_decode", recorded)
    with svc:
        calls = serve(cfg, router, n_ctx=2, calls=2)
        counters = router.stats()["telemetry"]["counters"]
    assert [len(c) for c in calls] == [5] * 4
    assert rounds
    assert counters["decode.pages_attended"] == sum(
        p // 16 + 1 for pos in rounds for p in pos)
    if in_place:
        assert counters["decode.inplace_rounds"] == len(rounds)
    else:
        assert "decode.inplace_rounds" not in counters


@pytest.mark.filterwarnings("error:Some donated buffers were not usable")
@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel", "gather"])
def test_arena_writes_donate_the_arenas(in_place, monkeypatch):
    """On a served run over the pool every program that writes the page
    arenas takes the buffers it is passed: the arena handle an
    ``admit16``, ``zero16`` or ``paged_extend`` was given is dead
    afterwards, as is a ``paged_decode``'s on the kernel's path (the
    gather path's round keeps its arguments), and ``exe.arena_donated``
    counts every such write, with no ``exe.arena_copied`` (the runtime
    never refused the alias: its warning is an error here)."""
    cfg, svc, router = make_served_hd128(monkeypatch, in_place,
                                         budget=8_000)
    exe = svc.exe
    assert exe.decode_donates is in_place
    passed = {n: [] for n in ("admit16", "zero16", "paged_extend",
                              "paged_decode")}
    for name, handles in passed.items():
        def kept(arenas, *a, _fn=getattr(exe, name), _handles=handles):
            _handles.append(next(iter(arenas.values())))
            return _fn(arenas, *a)
        monkeypatch.setattr(exe, name, kept)
    with svc:
        serve(cfg, router, n_ctx=3, calls=2)
        counters = router.stats()["telemetry"]["counters"]
    assert all(passed.values())
    for name, handles in passed.items():
        dead = in_place or name != "paged_decode"
        assert all(h.is_deleted() is dead for h in handles), name
    writes = sum(len(h) for n, h in passed.items()
                 if in_place or n != "paged_decode")
    assert counters["exe.arena_donated"] == writes > 0
    assert "exe.arena_copied" not in counters


def test_moe_decode_counters(monkeypatch):
    """A routed model's paged rounds (MLA + experts, on the pool's gather
    path): ``moe.decode_rounds`` counts each round, ``moe.experts_hit``
    adds the distinct experts its real rows route to in each routed
    layer, read from the expert ids the program returns; pad rows of a
    batch bucket are not counted."""
    cfg, model, params = tiny_model("deepseek-v2-lite-16b")
    sc = LLMSConfig(policy="llms", max_ctx_len=128, chunk_tokens=16,
                    memory_budget=10_000_000, decode_batch=4,
                    swap_dir=tempfile.mkdtemp())
    svc = LLMService(model, params, sc)
    assert svc.paged
    router = ServiceRouter(svc, predict=True, slice_steps=2)
    exe = svc.exe
    rounds = []
    fn = exe.paged_decode_fn

    def recorded(*a):
        out = fn(*a)
        real = np.asarray(a[3])[:, 0] > 0            # pt16: page 0 is pad
        n = int(real.sum())
        assert real[:n].all()                        # real rows come first
        rounds.append((n, np.asarray(out[-1])))
        return out
    monkeypatch.setattr(exe, "paged_decode_fn", recorded)
    with svc:
        serve(cfg, router, n_ctx=3, calls=2)
        counters = router.stats()["telemetry"]["counters"]
    n_moe, k = cfg.n_layers - cfg.moe.first_dense, cfg.moe.top_k
    assert rounds and any(n < 4 for n, _ in rounds)    # a padded bucket
    assert counters["moe.decode_rounds"] == len(rounds)
    hits = [[len(set(ids[l, :n].ravel())) for l in range(n_moe)]
            for n, ids in rounds]
    assert counters["moe.experts_hit"] == sum(map(sum, hits))
    for (n, ids), h in zip(rounds, hits):
        assert ids.shape == (n_moe, 4, k)
        assert all(1 <= x <= min(cfg.moe.n_experts, n * k) for x in h)
    assert counters["decode.pages_attended"] > 0


def test_reset_stats_resets_telemetry():
    cfg, svc, router = make_served(budget=10_000_000)
    with svc:
        serve(cfg, router, n_ctx=2, calls=1)
        assert router.stats()["telemetry"]["counters"]["calls_finished"] == 2
        router.reset_stats()
        tel = router.stats()["telemetry"]
        assert tel["spans"] == {} and tel["counters"] == {}
        serve(cfg, router, n_ctx=1, calls=1)
        tel = router.stats()["telemetry"]
    assert tel["counters"]["calls_finished"] == 1
    assert tel["spans"]["router.complete"]["count"] == 1
    assert 0 < tel["spans"]["service.begin_call"]["total_s"] <= tel[
        "elapsed_s"]


def test_dispatcher_thread_spans():
    """With its own dispatcher thread the router records when it waits
    on an empty queue and when it runs a batch: the two never overlap,
    so together they fit in the registry's elapsed time."""
    cfg, svc, _ = make_served(budget=10_000_000)
    with svc, ServiceRouter(svc, predict=False, start=True,
                            slice_steps=2) as router:
        app = router.register_app("app", "fg")
        stub = app.new_ctx()
        rng = np.random.RandomState(1)
        for _ in range(3):
            st = app.stream(stub, rng.randint(1, cfg.vocab, 8).tolist(),
                            max_new_tokens=4)
            assert len(st.result(timeout=120)) == 4
        tel = router.stats()["telemetry"]
    sp = tel["spans"]
    assert sp["router.dispatch"]["count"] >= 3
    assert sp["router.wait"]["count"] >= 3
    assert (sp["router.dispatch"]["total_s"] + sp["router.wait"]["total_s"]
            <= tel["elapsed_s"])
    assert sp["router.dispatch"]["self_s"] < sp["router.dispatch"]["total_s"]


def test_whole_state_recovery_counted_once():
    """A corrupt whole-state file (``swap`` policy) is rebuilt by
    recompute and counted once, as the registry's ``chunks_recovered``,
    which ``fault_stats`` reports as ``chunks_recovered_recompute``."""
    from repro.core.faults import FaultSpec, clear_faults, install_faults
    cfg, model, params = tiny_model("smollm-360m")
    install_faults([FaultSpec(kind="bit_flip", sites=("disk.write",),
                              rate=0.5)], seed=2024)
    sc = LLMSConfig(policy="swap", max_ctx_len=128, chunk_tokens=16,
                    memory_budget=12_000, swap_dir=tempfile.mkdtemp())
    svc = LLMService(model, params, sc)
    try:
        rng = np.random.RandomState(7)
        stubs = [svc.newLLMCtx() for _ in range(3)]
        for r in range(9):
            svc.callLLM(stubs[r % 3], rng.randint(1, cfg.vocab, size=12)
                        .tolist(), max_new_tokens=4)
        st = svc.stats()
        counters = svc.telemetry.snapshot()["counters"]
    finally:
        clear_faults()
        svc.close()
    assert st["chunks_corrupt_detected"] > 0
    assert (st["chunks_recovered_recompute"] == counters["chunks_recovered"]
            == st["chunks_corrupt_detected"])


def test_zoo_members_share_one_registry():
    from repro.core.zoo import ZooService
    _, model, params = tiny_model("smollm-360m")
    sc = LLMSConfig(policy="llms", max_ctx_len=128)
    zoo = ZooService({"a": (model, params, sc), "b": (model, params, sc)})
    with zoo:
        tels = {id(m.telemetry) for m in zoo.members.values()}
        tels |= {id(m.exe.tel) for m in zoo.members.values()}
        tels |= {id(zoo.store.telemetry), id(ServiceRouter(zoo).telemetry)}
    assert tels == {id(zoo.telemetry)}


# --------------------------------------------------------------------- #
# under the profiler
# --------------------------------------------------------------------- #
# the CPU backend's host event that runs a program and carries its
# run_id, on the dispatching thread (a TPU trace has DoEnqueueProgram
# instead, which the runtime may defer to a thread of its own)
ENQUEUE = "PjRtCpuExecutable::ExecuteHelper"


def test_spans_on_the_host_plane_of_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    cfg, svc, router = make_served()
    with svc:
        serve(cfg, router, n_ctx=2, calls=1)          # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            serve(cfg, router, n_ctx=3, calls=2, seed=5)
        finally:
            jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    spans, enq = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                      line.name)
                if ev.name.startswith("llms:"):
                    spans.append(iv)
                elif ev.name == ENQUEUE and any(
                        k == "run_id" for k, _ in ev.stats):
                    enq.append(iv)
    names = {s[2] for s in spans}
    assert {"llms:router.round", "llms:service.decode_round",
            "llms:decode.step", "llms:exe.paged_decode",
            "llms:service.begin_call", "llms:exe.paged_extend"} <= names

    def inside(iv, prefixes):
        return any(s[2].startswith(prefixes) and s[3] == iv[3]
                   and s[0] <= iv[0] and iv[1] <= s[1] for s in spans)

    # nested: each decode entry lies inside its service and router spans
    dec = [s for s in spans if s[2] == "llms:exe.paged_decode"]
    assert dec and all(inside(s, "llms:decode.step") for s in dec)
    assert all(inside(s, "llms:router.round") for s in dec)
    # every program run in the served window was dispatched inside an
    # executor entry's span
    lo = min(s[0] for s in spans)
    hi = max(s[1] for s in spans)
    served = [e for e in enq if lo <= e[0] <= hi]
    assert served
    outside = [e for e in served
               if not inside(e, ("llms:exe.", "llms:codec."))]
    assert outside == []
