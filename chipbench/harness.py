"""One run of one cell: set-up, the measured window, the drain, the
readers, and the comparison with the plain reference.

The program is driven from outside, through its public serving path:
``ServiceRouter`` (started, with its own dispatcher thread) over
``LLMService`` with the paged pool.  This module only submits calls,
watches their streams, and reads the router's and the service's
records.  With ``trace`` on it also wraps a few service methods in
``jax.profiler.TraceAnnotation`` spans and counts the shapes that
reach two program entry points (the paged extend and the chunk codec),
so that device programs can be tied to layers; with ``trace`` off it
touches nothing.
"""
from __future__ import annotations

import gc
import glob
import heapq
import importlib.util
import json
import shutil
import sys
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
WORK = CHECKOUT / ".chipbench"          # swap files, traces (git-ignored)

from chipbench.readlib import token_gaps_ms  # noqa: E402
from chipbench.traffic import generate as gen  # noqa: E402


def log(msg: str):
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# specification
# --------------------------------------------------------------------- #
def load_benchmark(root: Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((CHECKOUT / entry["file"]).read_text())


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict):
    """The configuration's plain reference module, ``configs/<ref>.py``."""
    ref = cfg["reference"]
    return _load_module(HERE / "configs" / f"{ref}.py",
                        f"chipbench_ref_{ref}")


def reader(metric: str):
    """The reader of one metric: ``metrics/<name>.py``'s ``read(obs)``."""
    return _load_module(HERE / "metrics" / f"{metric}.py",
                        "chipbench_metric_" + metric.replace(".", "_")).read


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The cell's metrics: its end-to-end ones without trace, its
    per-layer ones with trace."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


# --------------------------------------------------------------------- #
# compile counting
# --------------------------------------------------------------------- #
class CompileCounter:
    """Counts programs lowered (every jit cache miss) and compiled by the
    backend (a persistent-cache miss), through jax.monitoring."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.lowered = 0
        self.compiled = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.BACKEND:
            self.compiled += 1

    def snapshot(self):
        return self.lowered, self.compiled

    def close(self):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)


# --------------------------------------------------------------------- #
# the program's logits, as its own sampler sees them
# --------------------------------------------------------------------- #
def probe_sampling(ids: np.ndarray, sink: list):
    """Greedy sampling parameters whose sampler also records, for every
    token it picks, the logits at the probe vocabulary ``ids`` and at
    the picked token, as the program produced them."""
    from repro.core.requests import SamplingParams

    class ProbeSampling(SamplingParams):
        def make_sampler(self):
            def sample(logits):
                tok = int(np.argmax(logits))
                sink.append(np.append(logits[ids], logits[tok]))
                return tok
            return sample
    return ProbeSampling()


# --------------------------------------------------------------------- #
# one served call
# --------------------------------------------------------------------- #
@dataclass(eq=False)
class Served:
    kind: str                   # "history" | "warm" | "window"
    ctx: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0            # absolute perf_counter time it fell due
    orig_due: float = 0.0       # its due time in the schedule
    t_submit: float = 0.0
    t_begin: Optional[float] = None
    t_done: Optional[float] = None
    stream: object = None
    switch_s: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    token_times: List[float] = field(default_factory=list)
    ok: bool = False
    error: Optional[str] = None
    cancelled: bool = False
    logits: list = field(default_factory=list)   # probe logits per token


class Cell:
    """Builds the service for one cell and drives its calls."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.fam = family(cfg)
        self.sv = mix["service"]
        self.cs = int(self.sv.get("chunk_tokens", 16))
        self.max_ctx = int(self.sv["max_ctx"])
        self.n_slots = -(-self.max_ctx // self.cs) * self.cs
        self.served: Dict[int, List[Served]] = defaultdict(list)
        self.by_stream: Dict[int, Served] = {}
        self.phases: Dict[str, float] = {}
        self._cv = threading.Condition()
        self._completed: deque = deque()
        V = self.fam.sizes(cfg)["V"]
        self.probe_ids = np.random.default_rng([self.seed, 11]).choice(
            V, size=min(64, V), replace=False)

    # -- set-up ---------------------------------------------------------- #
    def kv_bytes_per_token(self) -> int:
        s = self.fam.sizes(self.cfg)
        return 2 * s["L"] * s["KV"] * s["hd"] * 2          # k and v, bf16

    def budget(self, sched) -> int:
        b = self.sv["budget"]
        if b["of"] == "histories":
            tokens = sum(len(h) for h in sched.histories)
        elif b["of"] == "full_contexts":
            tokens = sched.n_contexts * self.n_slots
        else:
            raise ValueError(b)
        return int(float(b["share"]) * tokens * self.kv_bytes_per_token())

    def build(self, sched, params):
        """Service + router over ``params``; one app per context."""
        from repro.configs.base import ModelConfig
        from repro.core.scheduler import ServiceRouter
        from repro.core.service import LLMSConfig, LLMService
        from repro.models.registry import build_model

        swap = WORK / "swap"
        shutil.rmtree(swap, ignore_errors=True)
        swap.mkdir(parents=True)
        mcfg = ModelConfig(**self.fam.program_config(self.cfg))
        self.model = build_model(mcfg)
        kw = dict(policy=self.sv.get("policy", "llms"),
                  max_ctx_len=self.max_ctx, memory_budget=self.budget(sched),
                  decode_batch=int(self.sv["decode_batch"]),
                  paged_pool=True, chunk_tokens=self.cs, swap_dir=str(swap))
        if "pool_pages_16" in self.sv:
            kw["pool_pages_16"] = int(self.sv["pool_pages_16"])
        self.svc = LLMService(self.model, params, LLMSConfig(**kw))
        assert self.svc.paged, "the cell needs the paged pool"
        self.router = ServiceRouter(self.svc, predict=True, start=True,
                                    slice_steps=int(self.sv["slice_steps"]))
        self.router.on_begin = self._on_begin
        self.router.on_complete = self._on_complete
        self.apps = [self.router.register_app(f"app{c}")
                     for c in range(sched.n_contexts)]
        self.stubs = [a.new_ctx() for a in self.apps]
        self.stubs_cid = [st.ctx_id for st in self.stubs]
        self.events = Events(self.svc)

    def _on_begin(self, job, resumed):
        s = self.by_stream.get(id(job["stream"]))
        if s is not None and s.t_begin is None:
            s.t_begin = job["t_start"]

    def _on_complete(self, job, cancelled):
        s = self.by_stream.get(id(job["stream"]))
        if s is None:
            return
        rec = self.router.call_records[-1] if self.router.call_records \
            else {}
        s.switch_s = rec.get("switch_s")
        s.cancelled = bool(cancelled)
        with self._cv:
            self._completed.append(s)
            self._cv.notify_all()

    def submit(self, s: Served):
        s.t_submit = time.perf_counter()
        st = self.apps[s.ctx].stream(
            self.stubs[s.ctx], s.prompt.tolist(), max_new_tokens=s.max_new,
            sampling=probe_sampling(self.probe_ids, s.logits))
        s.stream = st
        self.by_stream[id(st)] = s
        self.served[s.ctx].append(s)
        return st

    def wait(self, items: List[Served], timeout: float = 600.0):
        for s in items:
            s.stream.result(timeout)
            self.settle(s)

    def settle(self, s: Served):
        st = s.stream
        s.t_done = st.t_done
        s.tokens = list(st.tokens)
        s.token_times = list(st.token_times)
        s.error = None if st.error is None else repr(st.error)
        s.ok = (st.error is None and not st.cancelled
                and len(s.tokens) == s.max_new)

    def warm_codec(self):
        """Compile the chunk codec at every level the planner may pick,
        before the window (which levels a swap-out uses depends on the
        attention densities the traffic produces)."""
        import jax
        import jax.numpy as jnp
        from repro.core import compression as comp

        exe = self.svc.exe
        F = exe.n_layers * int(np.prod(exe.leaf_dims[exe.codec.leaves[0]]))
        blk = {n: jnp.zeros((self.cs, F), jnp.bfloat16)
               for n in exe.codec.leaves}
        for bits, _ in comp.DEFAULT_LEVELS:
            if bits < 16:
                cc = exe.codec.compress_blocks(blk, bits)
                jax.block_until_ready(exe.codec.decompress(cc))

    def setup(self, sched):
        """Histories, then warm calls until every prompt bucket and decode
        batch size of the cell has run."""
        limit = self.n_slots // 2
        t = time.perf_counter()
        for c, h in enumerate(sched.histories):
            for part in gen.history_parts(h, limit):
                s = Served("history", c, part, 0)
                self.submit(s)
                self.wait([s])
        self.phases["history_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.warm_codec()
        rng = np.random.default_rng([self.seed, 7])
        vocab = self.fam.sizes(self.cfg)["V"]
        # the longest prompt a condense re-encodes: the half window
        cond = Served("warm", 0, rng.integers(
            1, vocab, size=limit - 1).astype(np.int32), 1)
        singles = [Served("warm", w.ctx, w.prompt, w.max_new)
                   for w in sched.warm if w.max_new == 1] + [cond]
        for s in singles:
            self.submit(s)
            self.wait([s])
        batch = [Served("warm", w.ctx, w.prompt, w.max_new)
                 for w in sched.warm if w.max_new > 1]
        for s in batch:
            self.submit(s)
        self.wait(batch)
        self.phases["warm_s"] = time.perf_counter() - t
        bad = [s for c in self.served.values() for s in c if not s.ok]
        if bad:
            raise RuntimeError(f"{len(bad)} set-up calls failed: "
                               f"{bad[0].error}")

    # -- the window ------------------------------------------------------ #
    def run_window(self, sched, seconds: float, drain_s: float):
        """Offer the schedule: each call falls due at its time, or when
        the call before it on the same context completes, whichever is
        later.  -> (t0, t_end) of the window."""
        with self._cv:
            self._completed.clear()
        pending: Dict[int, deque] = defaultdict(deque)
        for call in sched.calls:
            pending[call.ctx].append(call)
        heap = []                                # (due_abs, idx, call)
        t0 = time.perf_counter()
        for c, q in pending.items():
            call = q.popleft()
            heapq.heappush(heap, (t0 + call.due, call.idx, call))
        self.window_calls: List[Served] = []
        t_end = t0 + seconds
        hard_end = t_end + drain_s
        left = len(sched.calls)
        while True:
            now = time.perf_counter()
            with self._cv:
                done = list(self._completed)
                self._completed.clear()
            for s in done:
                if s.kind != "window":
                    continue
                self.settle(s)
                left -= 1
                if pending[s.ctx]:
                    call = pending[s.ctx].popleft()
                    due = max(t0 + call.due, s.t_done)
                    heapq.heappush(heap, (due, call.idx, call))
            while heap and heap[0][0] <= now:
                due, _, call = heapq.heappop(heap)
                s = Served("window", call.ctx, call.prompt, call.max_new,
                           due=due, orig_due=t0 + call.due)
                self.window_calls.append(s)
                self.submit(s)
            now = time.perf_counter()
            if now >= t_end and (drain_s <= 0 or left == 0):
                break
            if now >= hard_end:
                break
            nxt = heap[0][0] if heap else hard_end
            if drain_s <= 0:
                nxt = min(nxt, t_end)
            with self._cv:
                if not self._completed:
                    self._cv.wait(max(0.0, min(nxt - now, 0.05)))
        return t0, t_end

    def stop(self):
        """End serving: cancel what is queued or in flight, stop the
        dispatcher, and settle every window call."""
        self.router.abort()
        for s in self.window_calls:
            if s.stream is not None:
                try:
                    s.stream.result(120.0)
                except Exception:
                    pass
                self.settle(s)

    def close(self):
        self.svc.close()
        self.router = self.svc = self.apps = self.stubs = None
        self.model = None
        gc.collect()

    # -- the context as the service holds it ----------------------------- #
    def replay(self, ref, params, ctx: int, want: List["Served"],
               weights: str = "bf16") -> Dict[int, np.ndarray]:
        """Replay context ``ctx`` in the plain reference up to the last
        call in ``want``: every call's segment in order, the condense
        the service ran (re-encoding its most recent tokens from
        position 0) and each chunk re-encode at the level the service's
        planner chose, each where the service ran it.  -> the reference
        logits of each wanted call's served tokens (by ``id``)."""
        cid = self.stubs_cid[ctx]
        groups = self.events.calls(cid)
        calls = self.served[ctx]
        assert len(groups) == len(calls), (len(groups), len(calls))
        last = max(calls.index(s) for s in want)
        store, toks, out = ref.new_store(), [], {}
        for s, g in zip(calls[:last + 1], groups):
            ext = [i for i, e in enumerate(g) if e[0] == "extend"]
            if len(ext) == 2:                  # condensed, then extended
                keep = g[ext[0]][2]
                toks = toks[len(toks) - keep:]
                store, _ = ref.extend(params, ref.new_store(), toks, 0,
                                      keep, weights=weights)
                for e in g[ext[0] + 1:ext[1]]:
                    store = ref.quantize(store, e[1], e[2])
            if g[ext[-1]][1:] != (len(toks), len(s.prompt)):
                raise RuntimeError(
                    f"context {ctx}: the service extended {g[ext[-1]][1:]},"
                    f" the replay expected {(len(toks), len(s.prompt))}")
            P, T = len(s.prompt), len(s.tokens)
            seg = [int(x) for x in s.prompt] + s.tokens
            at = np.arange(P - 1, P + T - 1) if s in want else ()
            store, lg = ref.extend(params, store, seg, len(toks),
                                   P + T - 1 if T else P, at,
                                   weights=weights)
            if lg is not None:
                out[id(s)] = lg
            for e in g[ext[-1] + 1:]:
                store = ref.quantize(store, e[1], e[2])
            toks = toks + seg
        return out


class Events:
    """What the service did to each context, in order, read from outside:
    a call began, a segment was extended at (n0, length), a chunk was
    re-encoded at a level.  Installed on the instances in every run (a
    list append per event); the plain reference replays these decisions
    and computes the values itself."""

    def __init__(self, svc):
        self.log: Dict[int, List[tuple]] = defaultdict(list)
        self._cur = None
        res, exe = svc.res, svc.exe
        begin, ext, enc = svc.begin_call, exe.paged_extend, \
            res._make_payload_paged

        def begin_call(stub, request):
            self.log[stub.ctx_id].append(("begin",))
            self._cur = stub.ctx_id
            try:
                return begin(stub, request)
            finally:
                self._cur = None

        def paged_extend(arenas, prompt, n0, *a, **k):
            self.log[self._cur].append(("extend", int(n0), len(prompt)))
            return ext(arenas, prompt, n0, *a, **k)

        def make_payload(ctx, i, bits, quant=False):
            self.log[ctx.cid].append(("encode", int(i), int(bits)))
            return enc(ctx, i, bits, quant=quant)

        svc.begin_call = begin_call
        exe.paged_extend = paged_extend
        res._make_payload_paged = make_payload

    def calls(self, cid: int) -> List[List[tuple]]:
        """The context's events, one list per call begun."""
        out: List[List[tuple]] = []
        for e in self.log[cid]:
            if e[0] == "begin":
                out.append([])
            elif out:
                out[-1].append(e)
        return out


# --------------------------------------------------------------------- #
# tracing helpers (only with --trace 1)
# --------------------------------------------------------------------- #
class Probes:
    """Spans around service methods and shape counters at two entry
    points, installed on the instances (the program's code is not
    changed)."""

    def __init__(self, cell: Cell):
        import jax

        self.extends: List[tuple] = []        # (t, n_new, n0)
        self.codec: List[tuple] = []          # (t, shape, bits, quantize)
        svc, res, exe = cell.svc, cell.svc.res, cell.svc.exe
        ann = jax.profiler.TraceAnnotation

        def span(obj, attr, name):
            fn = getattr(obj, attr)

            def wrapped(*a, **k):
                with ann("bench:" + name):
                    return fn(*a, **k)
            setattr(obj, attr, wrapped)

        span(svc, "begin_call", "service.begin_call")
        span(svc, "decode_step_batch", "service.decode_round")
        span(svc, "finish_call", "service.finish_call")
        span(svc, "prepare_switch", "service.prepare_switch")
        span(res, "switch_in", "residency.switch_in")

        ext = exe.paged_extend

        def paged_extend(arenas, prompt, n0, *a, **k):
            self.extends.append((time.perf_counter(), len(prompt), int(n0)))
            return ext(arenas, prompt, n0, *a, **k)
        exe.paged_extend = paged_extend

        q, dq = exe.codec._q, exe.codec._dq

        def quant(blk, bits):
            self.codec.append((time.perf_counter(), tuple(blk.shape),
                               int(bits), True))
            return q(blk, bits=bits)

        def dequant(packed, scale, bits, n_tokens, **k):
            self.codec.append((time.perf_counter(),
                               (int(n_tokens), int(packed.shape[1])),
                               int(bits), False))
            return dq(packed, scale, bits=bits, n_tokens=n_tokens, **k)
        exe.codec._q, exe.codec._dq = quant, dequant


# --------------------------------------------------------------------- #
# the correctness comparison
# --------------------------------------------------------------------- #
def sample_calls(calls: List[Served], seed: int, tokens: int,
                 max_calls: int) -> List[Served]:
    """Finished window calls drawn from the seed, the longest first,
    until ``tokens`` served tokens or ``max_calls`` calls."""
    ok = [s for s in calls if s.ok and s.tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda s: len(s.tokens))
    rest = [s for s in ok if s is not longest]
    order = np.random.default_rng([int(seed), 9]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= max_calls:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def compare(cell: Cell, params, sample: List[Served],
            controls=()) -> dict:
    """Logit gaps of the served tokens under the plain reference: at each
    position, how far the served token's reference logit lies below the
    reference's best.  With ``controls``, the same for the token that the
    reference computed at that lower precision puts first."""
    out_hi = cell.mix["output"]
    ref = cell.fam.Reference(cell.cfg, cell.n_slots, cell.cs,
                             int(out_hi.get("hi", out_hi.get("n", 1))))
    by_ctx: Dict[int, List[Served]] = defaultdict(list)
    for s in sample:
        by_ctx[s.ctx].append(s)

    def logits(weights):
        lg = {}
        for c, want in by_ctx.items():
            lg.update(cell.replay(ref, params, c, want, weights))
        return lg

    base = logits("bf16")
    ids = cell.probe_ids
    gaps, errs, top2 = [], [], []
    agree = 0
    for s in sample:
        lg = base[id(s)]
        T = len(s.tokens)
        rows = np.arange(T)
        served = np.asarray(s.tokens)
        g = lg.max(axis=1) - lg[rows, served]
        gaps.extend(float(x) for x in g)
        agree += int(np.sum(np.argmax(lg, axis=1) == served))
        prog = np.asarray(s.logits[:T], np.float32)
        errs.append(float(max(np.abs(prog[:, :-1] - lg[:, ids]).max(),
                              np.abs(prog[:, -1] - lg[rows, served]).max())))
        srt = np.sort(lg, axis=1)
        top2.extend(float(x) for x in srt[:, -1] - srt[:, -2])
    out = {"calls": len(sample), "tokens": len(gaps),
           "max_logit_gap": max(gaps) if gaps else None,
           "max_logit_err": max(errs) if errs else None,
           "mean_logit_gap": float(np.mean(gaps)) if gaps else None,
           "top1_share": agree / len(gaps) if gaps else None,
           # what serving the reference's second-best token everywhere
           # would read as the widest gap: the altered-token fault
           "fault_second_token_gap": max(top2) if top2 else None}
    for c in controls:
        ctl = logits(c)
        v, e = [], []
        for s in sample:
            lg, lc = base[id(s)], ctl[id(s)]
            rows = np.arange(len(lc))
            pick = np.argmax(lc, axis=1)
            v.extend(float(x) for x in lg.max(axis=1) - lg[rows, pick])
            e.append(float(max(np.abs(lc[:, ids] - lg[:, ids]).max(),
                               np.abs(lc[rows, pick] - lg[rows, pick]).max())))
        out[f"control_{c}"] = {"tokens": len(v), "max_logit_gap": max(v),
                               "max_logit_err": max(e),
                               "mean_logit_gap": float(np.mean(v))}
    return out


def judge(readings: dict, chk: dict):
    """The verdict on one set of compared numbers (the program's, or a
    control's in its place): every number at or under its limit, over
    enough served tokens.  -> (correct, the numbers beside their
    limits)."""
    checks = {name: {"value": readings.get(name), "limit": float(limit)}
              for name, limit in chk["limits"].items()}
    correct = (readings.get("tokens", 0) >= int(chk["min_tokens"])
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    checks["served_tokens_compared"] = {"value": readings.get("tokens", 0),
                                        "limit": int(chk["min_tokens"])}
    return correct, checks


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, log=log, controls=(), rate_per_s=None,
        cfg=None, mix=None):
    """-> (the result line's object, the control readings, programs
    (lowered, compiled) inside the window).  ``cfg``/``mix`` replace
    the cell's configuration and traffic files (tests at small sizes)."""
    import jax

    spec = cell_spec(bench, workload)
    cfg = cfg or load_config(bench, spec["config"])
    mix = mix or gen.load(spec["traffic"])
    counter = CompileCounter()
    cell = Cell(cfg, mix, seed)
    fam = cell.fam
    vocab = fam.sizes(cfg)["V"]
    sched = gen.build(mix, seconds, seed, vocab, rate_per_s=rate_per_s)
    log(f"schedule: {gen.describe(sched)}, "
        f"{len(sched.calls) / seconds} calls/s")

    t = time.perf_counter()
    params = fam.make_params(cfg, seed)
    jax.block_until_ready(params)
    cell.phases["init_s"] = t - t_start
    cell.phases["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    cell.build(sched, params)
    cell.phases["service_s"] = time.perf_counter() - t
    cell.setup(sched)
    from repro.core.restore import io_counters
    probes = Probes(cell) if trace else None
    cell.router.reset_stats()
    io0 = io_counters()
    lowered0, compiled0 = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    log("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in
                               cell.phases.items())
        + f"; programs lowered {lowered0}, compiled {compiled0}; "
          f"setup_s {setup_s}")

    trace_dir = WORK / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    drain_s = float(mix.get("drain_s", 0))
    trace_t0 = time.perf_counter()
    t0, t_end = cell.run_window(sched, seconds, drain_s)
    trace_t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    lowered1, compiled1 = counter.snapshot()
    counter.close()
    router_stats = cell.router.stats()
    io1 = io_counters()
    cell.stop()
    window_calls = cell.window_calls
    in_window = (lowered1 - lowered0, compiled1 - compiled0)
    lags = [s.t_submit - s.due for s in window_calls]
    log(f"window: {len(sched.calls)} calls due, {len(window_calls)} "
        f"submitted, {sum(s.ok for s in window_calls)} done, "
        f"{sum(s.error is not None for s in window_calls)} failed, "
        f"{sum(s.cancelled for s in window_calls)} cancelled at the end; "
        f"generator lag max {max(lags) if lags else 0.0} s; programs "
        f"lowered/compiled inside the window {in_window}")

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    obs = {"calls": window_calls, "t0": t0, "t_end": t_end,
           "window_s": t_end - t0, "seconds": seconds, "setup_s": setup_s,
           "router": router_stats, "io_read": io1["read"] - io0["read"],
           "io_write": io1["write"] - io0["write"], "cfg": cfg, "mix": mix,
           "decode_batch": int(mix["service"]["decode_batch"]),
           "drain": drain_s > 0, "trace": None, "probes": probes,
           "trace_t0": trace_t0, "trace_t1": trace_t1,
           "device_kind": dev.device_kind}
    log(f"router: decode_rounds {router_stats['decode_rounds']}, "
        f"decoded_tokens {router_stats['decoded_tokens']}; disk read "
        f"{obs['io_read']} B, written {obs['io_write']} B")
    if trace:
        from chipbench import trace_reduce
        path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
        obs["trace"] = trace_reduce.reduce(trace_reduce.load(path),
                                           trace_t1 - trace_t0)
        shutil.rmtree(trace_dir, ignore_errors=True)

    gaps = np.asarray(token_gaps_ms(obs))
    if gaps.size:
        q = np.percentile(gaps, [50, 90, 95, 99])
        log(f"token gaps: {gaps.size}, p50/p90/p95/p99 "
            f"{', '.join(f'{x:.2f}' for x in q)} ms; share over 1.25 x "
            f"p50 {float(np.mean(gaps > 1.25 * q[0]))}")

    metrics = {}
    for m in metrics_for(bench, workload, trace):
        v = reader(m["name"])(obs)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # program state freed before the reference runs
    cell.close()
    failed = sum(s.error is not None for s in window_calls)
    chk = mix["check"]
    sample = sample_calls(window_calls, seed, int(chk["tokens"]),
                          int(chk["max_calls"]))
    t = time.perf_counter()
    cmp = compare(cell, params, sample, controls)
    log(f"reference: {cmp['calls']} calls, {cmp['tokens']} served tokens "
        f"compared in {time.perf_counter() - t:.1f} s; top-1 share "
        f"{cmp['top1_share']}")
    correct, checks = judge(cmp, chk)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(sched.calls),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        tr = obs["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    # each control judged as the program is: it has to come out not correct
    extra = {k: dict(v, correct=judge(v, chk)[0])
             for k, v in cmp.items() if k.startswith("control_")}
    done = [s for s in window_calls if s.ok]
    extra["window"] = {
        "due": len(sched.calls), "done": len(done),
        "done_in_window": sum(s.t_done <= t_end for s in done),
        "last_done_after_window_s": max(
            (s.t_done - t_end for s in done), default=0.0),
        "lag_max_s": max(lags) if lags else 0.0,
        "top1_share": cmp["top1_share"],
        "mean_logit_gap": cmp["mean_logit_gap"],
        "max_logit_gap": cmp["max_logit_gap"],
        "max_logit_err": cmp["max_logit_err"],
        "fault_second_token_gap": cmp["fault_second_token_gap"],
        "phases": cell.phases}
    return result, extra, in_window


def print_checks(checks: dict):
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
