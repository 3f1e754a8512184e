"""LLMService — the LLMaaS system service (paper Table 1, §3).

Thin facade over the four-layer serving stack (DESIGN.md §1):
``executor.ModelExecutor`` (jitted entry points + bucket/padding),
``context_store.ContextStore`` (persistent contexts, Fig. 4),
``residency.ResidencyEngine`` (switch-in/out, compression, AoT,
eviction), with ``scheduler.ServiceRouter`` as the multi-app front-end
on top.  The paper's full design plus every baseline it compares
against (VLLM-S/SQ, whole-context Swapping, LMK, and the three
ablations) are POLICIES of this one facade so benchmarks measure
like-for-like.  The measured *context switching latency* (Fig. 9) is
the time of ``ResidencyEngine.switch_in`` — the paper's QoS metric.

The request path is stepwise (DESIGN.md §2): ``begin_call`` claims a
decode slot, switches the context in and prefills the prompt;
``decode_step`` emits one token; ``decode_step_batch`` emits one token
for EACH of up to ``decode_batch`` resident generations through a
single jitted batched step; ``finish_call`` compresses/AoT-swaps the
result out and parks the slot.  The router runs generations in bounded
decode slices and may ``suspend_call`` / ``resume_call`` between
slices — preemption evicts one slot (a real, measured context switch
riding the ResidencyEngine) while the rest of the batch keeps
decoding.  ``callLLM`` is the Table-1 compat shim over the same path;
with ``decode_batch=1`` and default ``SamplingParams`` (temperature=0
greedy) it is token-for-token identical to the pre-batch serial
implementation (the singleton path routes through the very same jitted
``decode`` callable).
"""
from __future__ import annotations

import tempfile
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.markers import requires_serialized
from repro.core import compression as comp
from repro.core.context_store import Context, ContextStore, LLMCtxStub  # noqa: F401 (re-export)
from repro.core.executor import ModelExecutor
from repro.core.lifecycle import LCTRUQueue, MemoryManager
from repro.core.requests import GenerationRequest, SamplingParams
from repro.core.residency import ResidencyEngine
from repro.core.swap import AsyncSwapper, DiskStore
from repro.core.telemetry import Telemetry
from repro.models.api import ModelBase

POLICIES = ("llms", "llms_nocomp", "llms_nopipe", "llms_nolife",
            "vllm_s", "vllm_sq", "swap", "lmk")

# policy -> (compression, use_pipeline, use_lctru, use_aot, chunked, use_disk)
_POLICY_FLAGS = {
    "llms":        ("tolerance", True, True, True, True, True),
    "llms_nocomp": ("none", True, True, True, True, True),
    "llms_nopipe": ("tolerance", False, True, True, True, True),
    "llms_nolife": ("tolerance", True, False, False, True, True),
    "vllm_s":      ("none", False, False, False, True, True),
    "vllm_sq":     ("static8", False, False, False, True, True),
    "swap":        ("none", False, False, False, False, True),
    "lmk":         ("none", False, False, False, False, False),
}


@dataclass
class LLMSConfig:
    policy: str = "llms"
    decode_batch: int = 1                  # working-cache decode slots (B)
    # quant-resident decode (DESIGN.md §2): compressed chunks stay int8
    # in the working cache and attention dequantizes in place (fused
    # kernel), instead of materializing bf16 copies at switch-in.
    # 8-bit (Eq. 3) chunks become directly decodable payloads; 4/2-bit
    # chunks stay packed and re-grid behind the same kernel.  Requires a
    # chunked policy and a family whose KVSpec declares quant_resident.
    quant_resident: bool = False
    # paged, unified KV pool (DESIGN.md §1/§4): dense-family contexts
    # decode as page-table views into one global chunk-granular page
    # arena — switch-in for a pool-resident context is a table read, and
    # batch membership changes cost a table-row swap (true continuous
    # batching).  On by default; families/policies that can't page fall
    # back to slot caches transparently.  pool_pages_* override the
    # arena sizes in pages (0 = auto).
    paged_pool: bool = True
    pool_pages_16: int = 0
    pool_pages_8: int = 0
    chunk_tokens: int = 16
    # bound the retained per-call timing records (scale harness: 10^5+
    # calls would grow ``records`` without bound).  None = keep all;
    # stats percentiles then cover the retained window while ``calls``
    # stays cumulative.
    record_limit: Optional[int] = None
    levels: Tuple[Tuple[int, float], ...] = comp.DEFAULT_LEVELS
    ratio_global: float = 0.5
    memory_budget: int = 64 << 20
    max_ctx_len: int = 512
    max_contexts_per_app: int = 8          # K in the paper
    swap_dir: Optional[str] = None
    # fault tolerance (DESIGN.md §6): transient-IO retry budget per op,
    # and the per-swap watchdog deadline (seconds; None = wait forever)
    # that turns a wedged swap into a SwapTimeoutError the router
    # converts into a preemption
    io_retries: int = 3
    io_retry_base_s: float = 0.002
    swap_deadline_s: Optional[float] = None
    window: int = 0
    n_sinks: int = 0
    compression: str = ""
    use_pipeline: bool = False
    use_lctru: bool = False
    use_aot: bool = False
    chunked: bool = False
    use_disk: bool = False

    def __post_init__(self):
        assert self.policy in POLICIES, self.policy
        assert self.decode_batch >= 1, self.decode_batch
        (self.compression, self.use_pipeline, self.use_lctru, self.use_aot,
         self.chunked, self.use_disk) = _POLICY_FLAGS[self.policy]
        if self.quant_resident and not self.chunked:
            raise ValueError(
                f"quant_resident requires a chunked policy, not "
                f"{self.policy!r} (whole-state caches have no chunk "
                "segments to leave quantized)")
        if not self.chunked:
            self.paged_pool = False     # pages ARE chunks


@dataclass
class GenerationState:
    """One in-flight generation between ``begin_call`` and
    ``finish_call``.  While ``suspended`` the working cache is swapped
    out (``cache is None``) and the pending sampled token plus the
    request's RNG live here, so ``resume_call`` continues the exact
    decode the preemption interrupted."""
    ctx: Context
    request: GenerationRequest
    sampler: Any
    prompt_len: int
    cache: Any = None
    slot: Optional[int] = None              # decode slot while resident
    next_tok: Optional[int] = None          # sampled, not yet emitted
    generated: List[int] = field(default_factory=list)
    t_switch: float = 0.0
    t_assemble: float = 0.0
    t_infer: float = 0.0
    n_preempts: int = 0
    suspended: bool = False
    done: bool = False

    @property
    def exhausted(self) -> bool:
        """No more tokens to emit (budget reached or max_new == 0)."""
        return self.next_tok is None


class LLMService:
    """One shared model + per-app persistent contexts (LLMaaS)."""

    def __init__(self, model: ModelBase, params, cfg: LLMSConfig, *,
                 store: Optional[DiskStore] = None,
                 swapper: Optional[AsyncSwapper] = None,
                 queue: Optional[LCTRUQueue] = None,
                 mem: Optional[MemoryManager] = None,
                 cid_alloc: Any = None,
                 records: Any = None,
                 telemetry: Optional[Telemetry] = None):
        # the keyword-only substrate arguments let a ZooService run
        # several family executors against ONE disk store / swapper /
        # LCTRU queue / byte budget / cid space / records stream /
        # span registry (DESIGN.md §4); standalone construction builds
        # private ones.
        self.model, self.params, self.cfg = model, params, cfg
        self.telemetry = tel = (telemetry if telemetry is not None
                                else Telemetry())
        self.exe = ModelExecutor(model, params, cfg, tel)
        if store is None:
            root = cfg.swap_dir or tempfile.mkdtemp(prefix="llms_swap_")
            store = DiskStore(root, tel)
        self.store = store
        self._owns_swapper = swapper is None
        self.swapper = swapper if swapper is not None else AsyncSwapper(
            self.store, retries=cfg.io_retries,
            retry_base_s=cfg.io_retry_base_s)
        self.queue = (queue if queue is not None
                      else LCTRUQueue(lru_only=not cfg.use_lctru))
        self.mem = (mem if mem is not None
                    else MemoryManager(cfg.memory_budget, self.queue))
        self.ctxs = ContextStore(self.mem, self.store, self.exe.s_work,
                                 cid_alloc=cid_alloc)
        self.res = ResidencyEngine(self.exe, self.ctxs, self.store,
                                   self.swapper, self.queue, self.mem, cfg,
                                   tel)
        self.records: Any = (records if records is not None
                             else (deque(maxlen=cfg.record_limit)
                                   if cfg.record_limit else []))
        self.total_calls = 0                  # cumulative (records may be
        self._t_switch_sum = 0.0              # a bounded window)
        # cid -> (cache, epoch) of parked decode slots: working-cache
        # reuse, one entry per idle slot (MRU last).  Mirrors
        # ``res.slots.idle`` — the SlotAllocator decides WHICH parked
        # slot to reclaim, this holds WHAT it cached.
        self._reuse: "OrderedDict[int, Tuple[Any, int]]" = OrderedDict()
        # paged mode: generations are views into the unified KV pool
        # (st.cache stays None); batch membership is carried by
        # page-table rows, so there is no merged-cache state to manage
        self.paged = self.exe.paged
        self._closed = False

    @property
    def decode_batch(self) -> int:
        """Number of working-cache decode slots (B)."""
        return self.exe.decode_slots

    @property
    def _active(self) -> Optional[Tuple[int, Any, int]]:
        """Compat view of the most-recently-parked slot as the old
        single-entry (cid, cache, epoch) reuse tuple."""
        if not self._reuse:
            return None
        cid, (cache, epoch) = next(reversed(self._reuse.items()))
        return (cid, cache, epoch)

    def _drop_reuse(self, cid: int):
        self._reuse.pop(cid, None)

    @property
    def contexts(self) -> Dict[int, Context]:
        return self.ctxs.contexts

    @property
    def n_slots(self) -> int:
        return self.exe.n_slots

    @requires_serialized
    def newLLMCtx(self, system_prompt: Optional[Sequence[int]] = None
                  ) -> LLMCtxStub:
        ctx = self.ctxs.create()
        stub = LLMCtxStub(ctx.cid)
        if system_prompt is not None and len(system_prompt):
            self.callLLM(stub, system_prompt, max_new_tokens=0)
        return stub

    @requires_serialized
    def delLLMCtx(self, stub: LLMCtxStub):
        self.ctxs.delete(stub.ctx_id)   # raises on busy: nothing changed
        # give the slot back and drop its reuse entry: a stale cache for
        # a deleted context would pin a full bf16 slot in memory
        self._drop_reuse(stub.ctx_id)
        self.res.slots.release(stub.ctx_id)
        if self.paged:                  # return its pages + page table
            self.res.pool.drop(stub.ctx_id)

    def bindLLMService(self, app: Any = None) -> "LLMService":
        return self

    # ------------------------------------------------------------------ #
    # stepwise request path: begin / decode / (suspend / resume) / finish
    # ------------------------------------------------------------------ #
    @requires_serialized
    def begin_call(self, stub: LLMCtxStub,
                   request: GenerationRequest) -> GenerationState:
        """Admit one request on a context: condense on overflow, switch
        the context in (the measured QoS path), prefill the prompt, and
        sample the first token.  Nothing is emitted yet — the first
        ``decode_step`` emits it."""
        with self.telemetry.span("service.begin_call", ctx=stub.ctx_id):
            ctx = self.ctxs.get(stub.ctx_id)
            if ctx.busy:
                # a suspended (slice-preempted) generation owns this
                # context's token tail; starting another call would let
                # condense/append rewrite state out from under it.  The
                # router avoids this ordering (same-context arrivals
                # don't preempt); reaching it means the app raced two
                # requests on one context.
                raise RuntimeError(
                    f"ctx {ctx.cid} has a suspended in-flight generation; "
                    "await or cancel its stream before a new call")
            prompt = np.asarray(request.prompt, np.int32)
            total_new = len(prompt) + request.max_new_tokens
            assert total_new <= self.exe.max_request_tokens, \
                "exceeds half window"
            if ctx.n_tokens + total_new > self.exe.n_slots:
                self._condense(ctx, keep=self.exe.n_slots // 2)

            st = GenerationState(ctx=ctx, request=request,
                                 sampler=request.sampling.make_sampler(),
                                 prompt_len=len(prompt))
            self._switch_in(st)
            try:
                # inference: extend with the new prompt (prefill)
                with self.telemetry.span("service.prefill", ctx=ctx.cid):
                    t1 = time.perf_counter()
                    n0 = ctx.n_tokens
                    ctx.tokens[n0:n0 + len(prompt)] = prompt
                    if self.paged:
                        pool = self.res.pool
                        cs = self.exe.cs
                        self.res.ensure_extend_range(
                            ctx, n0 // cs, (n0 + len(prompt) - 1) // cs)
                        pt16, pt8, qmask = pool.rows([ctx.cid])
                        pool.arenas, logits, dens = self.exe.paged_extend(
                            pool.arenas, prompt, n0, pt16, pt8, qmask)
                    else:
                        cache, logits, dens = self.exe.extend(
                            st.cache, prompt, n0)
                        st.cache = cache
                    self.ctxs.acc_density(ctx, dens, n0 + len(prompt))
                    ctx.n_tokens += len(prompt)
                    if request.max_new_tokens > 0:
                        st.next_tok = st.sampler(logits)
                    st.t_infer += time.perf_counter() - t1
                ctx.busy += 1
            except BaseException:   # failed prefill must not leak the slot
                self.res.slots.release(ctx.cid)
                st.slot = st.cache = None
                raise
            return st

    @requires_serialized
    def decode_step(self, st: GenerationState) -> Optional[int]:
        """Emit the pending token and (if budget remains) run one decode
        step to sample the next.  -> the emitted token, or None when the
        generation is exhausted."""
        return self.decode_step_batch([st])[0]

    @requires_serialized
    def decode_step_batch(self, sts: Sequence[GenerationState]
                          ) -> List[Optional[int]]:
        """One decode round over up to ``decode_batch`` resident
        generations: emit each state's pending token, then run a single
        batched decode step for every state with budget remaining (a
        lone survivor routes through the serial ``decode`` — so with
        decode_batch=1 this IS the serial path, token for token).
        -> emitted tokens parallel to ``sts`` (None where exhausted)."""
        with self.telemetry.span("service.decode_round"):
            t1 = time.perf_counter()
            out: List[Optional[int]] = []
            live: List[GenerationState] = []
            fed: List[int] = []
            for st in sts:
                if st.done or st.next_tok is None:
                    out.append(None)
                    continue
                assert not st.suspended, "resume_call before decode_step"
                ctx = st.ctx
                tok = st.next_tok
                st.generated.append(tok)
                ctx.tokens[ctx.n_tokens] = tok
                ctx.n_tokens += 1
                out.append(tok)
                if len(st.generated) >= st.request.max_new_tokens:
                    # the final emitted token is appended to the text but
                    # never fed (no decode round left): its KV row stays
                    # zero.  Track the hole so recompute-based fault
                    # recovery skips the token too (DESIGN.md §6).
                    ctx.kv_holes.add(ctx.n_tokens - 1)
                    st.next_tok = None
                else:
                    live.append(st)
                    fed.append(tok)
            if live:
                if self.paged:
                    self._decode_round_paged(live, fed)
                else:
                    # slot mode decodes members serially: the pool carries
                    # the batched path, and non-paged families don't support
                    # per-row positions in one jitted step
                    for st, tok in zip(live, fed):
                        cache, logits, mass = self.exe.decode(st.cache, tok)
                        st.cache = cache
                        self.ctxs.acc_density(st.ctx, mass, st.ctx.n_tokens)
                        st.next_tok = st.sampler(logits)
            n_stepped = sum(tok is not None for tok in out)
            if n_stepped:
                share = (time.perf_counter() - t1) / n_stepped
                for st, tok in zip(sts, out):
                    if tok is not None:
                        st.t_infer += share
            return out

    def _decode_round_paged(self, live: List[GenerationState],
                            fed: List[int]):
        """One continuous-batching round over the pool: each live
        generation contributes its page-table row and its own position —
        membership changes between rounds swap table rows, never caches
        (no merge/split)."""
        pool = self.res.pool
        cs = self.exe.cs
        tel = self.telemetry
        with tel.span("decode.prepare"):
            pos = []
            for st in live:
                p = st.ctx.n_tokens - 1         # the just-emitted token
                self.res.ensure_tail(st.ctx, p // cs)
                pool.touch(st.ctx.cid)
                pos.append(p)
            pt16, pt8, qmask = pool.rows([st.ctx.cid for st in live])
        with tel.span("decode.step"):
            pool.arenas, logits, mass = self.exe.paged_decode(
                pool.arenas, fed, pos, pt16, pt8, qmask)
        with tel.span("decode.density"):
            for i, st in enumerate(live):
                self.ctxs.acc_density(st.ctx, mass[i], st.ctx.n_tokens)
        with tel.span("decode.sample"):
            for i, st in enumerate(live):
                st.next_tok = st.sampler(logits[i])

    @requires_serialized
    def suspend_call(self, st: GenerationState):
        """Preempt an in-flight generation: commit the partial result
        (compress + AoT swap-out, exactly a switch-out) and park its
        decode slot — the rest of a batch keeps decoding.  The
        sampled-but-unemitted token stays in the state, so resume
        continues the interrupted decode."""
        assert not (st.suspended or st.done)
        self._swap_out(st)
        self._park(st)
        st.suspended = True
        st.n_preempts += 1

    @requires_serialized
    def _park(self, st: GenerationState):
        """Slot held -> idle.  Slot mode keeps the cache for exact-reuse
        resume; paged-persist mode records only the epoch — the pages
        themselves stay in the pool, so the entry just marks the context
        warm (decode-ready) until an eviction invalidates it."""
        if not self.paged:
            self._reuse[st.ctx.cid] = (st.cache, self.res.epoch)
            self._reuse.move_to_end(st.ctx.cid)
        elif self.res.pool_persist and not self.res.force_dequant:
            self._reuse[st.ctx.cid] = (None, self.res.epoch)
            self._reuse.move_to_end(st.ctx.cid)
        self.res.slots.park(st.ctx.cid)
        st.cache = None
        st.slot = None

    @requires_serialized
    def resume_call(self, st: GenerationState):
        """Switch a suspended generation's context back in — a real,
        measured context switch (accumulated into the call's switch_s)."""
        assert st.suspended and not st.done
        st.suspended = False
        try:
            self._switch_in(st)
        except BaseException:
            # stay suspended: the router may requeue and retry the
            # resume (e.g. after a watchdog preemption) — a state that
            # claims residency without a slot would misroute it
            st.suspended = True
            raise

    @requires_serialized
    def finish_call(self, st: GenerationState) -> List[int]:
        """Compress / AoT swap-out / reclaim (paper §3.2 + §3.4) and
        append the per-call timing record.  Safe on a suspended state
        (cancel-after-preempt): the partial result is already out.  The
        busy/record bookkeeping runs even if the swap-out fails, so an
        errored call never bricks its context."""
        ctx = st.ctx
        with self.telemetry.span("service.finish_call", ctx=ctx.cid):
            try:
                if not st.suspended:
                    self._swap_out(st)
                    self._park(st)
            finally:
                if st.slot is not None:     # park failed: free the slot
                    self.res.slots.release(ctx.cid)
                    st.slot = None
                st.cache = None
                st.done = True
                ctx.busy -= 1
                self.total_calls += 1
                self._t_switch_sum += st.t_switch
                self.records.append({
                    "ctx": ctx.cid, "switch_s": st.t_switch,
                    "infer_s": st.t_infer + st.t_assemble,
                    "assemble_s": st.t_assemble,
                    "new_tokens": st.prompt_len + len(st.generated),
                    "n_preempts": st.n_preempts,
                    "mem_used": self.mem.used,
                })
                self.telemetry.count("calls_finished")
        return st.generated

    @requires_serialized
    def _swap_out(self, st: GenerationState):
        """Compress + AoT swap-out of the generation's context, then the
        reclaim that brings memory back under the budget."""
        tel, cid = self.telemetry, st.ctx.cid
        with tel.span("residency.swap_out", ctx=cid):
            self.res.compress_and_swap_out(st.ctx, st.cache)
        with tel.span("residency.reclaim", ctx=cid):
            self.mem.reclaim(0, self.res.evict, locked=set())

    @requires_serialized
    def _switch_in(self, st: GenerationState):
        """Claim a decode slot and switch the context in (the measured
        QoS metric): missing-state restore is timed; resident assembly
        is inference (DESIGN.md §2).  A parked slot still caching this
        context (and untouched by eviction since — epoch match) is the
        zero-restore fast path."""
        ctx = st.ctx
        t0 = time.perf_counter()
        entry = self._reuse.pop(ctx.cid, None)
        st.slot = self.res.slots.acquire(ctx.cid, self._drop_reuse)
        # paged mode never short-circuits: pages may have been dropped
        # on re-encode at swap-out, and switch_in is where stale table
        # entries are re-admitted — it is already near-free when the
        # pages survived (a table read)
        if (not self.paged and entry is not None
                and entry[1] == self.res.epoch):
            st.cache = entry[0]
            st.t_switch += time.perf_counter() - t0
        else:
            try:
                with self.telemetry.span("residency.switch_in", ctx=ctx.cid):
                    cache, t_sw = self.res.switch_in(ctx)
            except BaseException:
                self.res.slots.release(ctx.cid)
                st.slot = None
                raise
            st.cache = cache
            st.t_switch += t_sw
            st.t_assemble += time.perf_counter() - t0 - t_sw

    # ------------------------------------------------------------------ #
    # Table-1 compat shim: one blocking call over the stepwise path
    # ------------------------------------------------------------------ #
    @requires_serialized
    def callLLM(self, stub: LLMCtxStub, new_prompt: Sequence[int],
                max_new_tokens: int = 16,
                sampling: Optional[SamplingParams] = None
                ) -> Tuple[LLMCtxStub, List[int]]:
        request = GenerationRequest(prompt=new_prompt,
                                    max_new_tokens=max_new_tokens,
                                    sampling=sampling or SamplingParams())
        st = self.begin_call(stub, request)
        while self.decode_step(st) is not None:
            pass
        self.finish_call(st)
        return stub, st.generated

    # scheduler hook (§3.4 prediction-driven AoT swap-out)
    @requires_serialized
    def prepare_switch(self, predicted_cid: int) -> int:
        return self.res.prepare_switch(predicted_cid)

    @requires_serialized
    def _condense(self, ctx: Context, keep: int):
        """Context overflow: re-encode the recent tail at [0, keep)."""
        with self.telemetry.span("service.condense", ctx=ctx.cid):
            tail = self.ctxs.reset_for_condense(ctx, keep, self.exe.cs)
            # the rebuilt state invalidates any parked slot cache of THIS ctx
            self._drop_reuse(ctx.cid)
            self.res.slots.release(ctx.cid)
            ctx.tokens[:len(tail)] = tail
            if self.paged:
                # the rebuilt state also invalidates every page this ctx held
                pool = self.res.pool
                pool.drop(ctx.cid)
                self.res.ensure_extend_range(ctx, 0,
                                             (len(tail) - 1) // self.exe.cs)
                pt16, pt8, qmask = pool.rows([ctx.cid])
                pool.arenas, _, dens = self.exe.paged_extend(
                    pool.arenas, np.asarray(tail, np.int32), 0,
                    pt16, pt8, qmask)
                self.ctxs.acc_density(ctx, dens, len(tail))
                ctx.n_tokens = len(tail)
                self.res.compress_and_swap_out(ctx, None)
            else:
                cache = self.exe.fresh_cache(0)
                cache, _, dens = self.exe.extend(cache, tail, 0)
                self.ctxs.acc_density(ctx, dens, len(tail))
                ctx.n_tokens = len(tail)
                self.res.compress_and_swap_out(ctx, cache)

    @requires_serialized
    def profile_pipeline(self, n_points: Tuple[int, ...] = (1, 2, 4)):
        self.res.profile_pipeline(n_points)

    def decode_ready_contexts(self) -> int:
        """Contexts whose next switch-in needs neither dequantization
        nor disk I/O: generations holding a slot, parked slots whose
        cached state survived every eviction since (epoch match), and —
        with the quant-resident tier on — every context whose chunks
        are all in memory (assembly is then a pure int8 scatter)."""
        ready = set(self.res.slots.held)
        for cid, (_, epoch) in self._reuse.items():
            if epoch == self.res.epoch:
                ready.add(cid)
        if self.exe.quant_resident and not self.res.force_dequant:
            for cid, ctx in self.contexts.items():
                # scatter-ready means every chunk's decode-grid codes
                # already exist: as the payload itself (m.quant) or as
                # the AoT re-grid memo — a packed chunk freshly restored
                # from disk has neither until its next switch-out
                if (ctx.n_tokens and ctx.chunks
                        and all(m.in_memory and m.bits != 16
                                and (m.quant or i in ctx.qmemo)
                                for i, m in ctx.chunks.items())):
                    ready.add(cid)
        return len(ready)

    def stats(self) -> Dict[str, float]:
        from repro.core.restore import io_counters
        sw = [r["switch_s"] for r in self.records]
        n_quant = sum(1 for ctx in self.contexts.values()
                      for m in ctx.chunks.values()
                      if m.in_memory and m.quant)
        io = io_counters()
        out = {
            "calls": len(sw),
            "total_calls": self.total_calls,
            "switch_mean_s": float(np.mean(sw)) if sw else 0.0,
            "switch_p99_s": float(np.percentile(sw, 99)) if sw else 0.0,
            "switch_total_s": self._t_switch_sum,
            "mem_used": self.mem.used,
            "disk_bytes": self.store.total_bytes,
            "disk_bytes_read": io["read"],        # process-cumulative
            "disk_bytes_written": io["write"],    # (see restore.count_io)
            "decode_slots": self.decode_batch,
            "slots_held": len(self.res.slots.held),
            "decode_ready_contexts": self.decode_ready_contexts(),
            "quant_resident_chunks": n_quant,
            "paged_pool": bool(self.paged),
        }
        if self.paged:
            out.update(self.res.pool.stats())
        # fault/detect/recover/degrade counters (DESIGN.md §6); the
        # per-kind injection breakdown stays nested under
        # "faults_injected"
        out.update(self.res.fault_stats())
        return out

    def close(self):
        """Idempotent; flushes pending AoT writes before shutdown so an
        interrupted swap-out never loses committed chunks.  Failed jobs
        were already classified/counted on the workers, and a wedged job
        is abandoned at the watchdog deadline — close never raises or
        hangs on a storage fault."""
        if self._closed:
            return
        self._closed = True
        if self._owns_swapper:      # a zoo shuts the shared swapper once
            self.swapper.shutdown(timeout=self.cfg.swap_deadline_s)

    def __enter__(self) -> "LLMService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
