"""Residency engine — switch-in/switch-out of context state (paper §3).

Layer 3 of the four-layer design (DESIGN.md §1): decides where every
chunk lives (bf16 working cache / compressed DRAM / disk) and moves it.
Switch-in plans the I/O-vs-recompute split (Eq. 4), dispatches the
layer-pipelined restore (Fig. 8), and assembles resident chunks into
one working-cache SLOT.  Switch-out runs tolerance-aware compression
(Eq. 1-3) and ahead-of-time swap-out (§3.4).  Eviction implements the
Reclaim primitive over the LCTRU order.

The paper prototype's working-set lock (one resident context) is
generalized to a ``SlotAllocator`` over ``decode_batch`` slots: up to B
contexts hold bf16 slot caches simultaneously and decode as one batch,
while the LCTRU queue and the compressed-chunk byte budget stay GLOBAL
across slots — eviction pressure from one slot's restore can reclaim
any context's chunks.  Preempting a generation evicts ONE slot (its
context switches out through the same compress/AoT path), not the
whole engine.

Built on ``lifecycle`` (eviction order + budget), ``swap`` (async disk
tier), and ``restore`` (segmented chunk files + LayerFeed); runs the
model only through the ``ModelExecutor``.
"""
from __future__ import annotations

import errno
import math
import os
import time
from collections import OrderedDict
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.markers import requires_serialized
from repro.analysis.runtime import witness_lock
from repro.core import compression as comp
from repro.core.chunks import ChunkMeta, CompressedChunk, QuantResidentChunk
from repro.core.context_store import Context, ContextStore
from repro.core.executor import ModelExecutor
from repro.core.faults import (FAULTS, ChunkCorruptError, DiskFullError,
                               SwapTimeoutError, with_retries)
from repro.core.lifecycle import LCTRUQueue, MemoryManager
from repro.core.pagepool import BF16, QUANT, PagePool
from repro.core.pipeline import PipelineProfile, fit_linear, plan_split
from repro.core.restore import (LayerFeed, read_chunk_file,
                                verify_chunk_file, write_chunk_file)
from repro.core.swap import AsyncSwapper, DiskStore


class SlotAllocator:
    """The working-set "lock" generalized to B decode slots.

    Each slot holds one context's bf16 working cache.  A slot is HELD
    while a generation is resident on it (between switch-in and
    switch-out/suspend); switching out PARKS the slot — the cache stays
    resident, keyed by context id, so an immediate resume or follow-up
    call on the same context reuses it with zero restore (the old
    single-entry ``_active`` fast path, now one per slot).  Acquiring a
    slot when none is free reclaims the least-recently-parked idle slot
    (its cached state is dropped — the context's chunks are already
    committed, so nothing is lost).  Holding more than B slots is a
    scheduler bug and raises."""

    def __init__(self, n_slots: int):
        self.n_slots = max(1, int(n_slots))
        self._free = list(range(self.n_slots - 1, -1, -1))
        self.held: Dict[int, int] = {}                   # cid -> slot
        self.idle: "OrderedDict[int, int]" = OrderedDict()  # cid -> slot, LRU

    def acquire(self, cid: int,
                on_evict: Optional[Callable[[int], None]] = None) -> int:
        """Claim a slot for ``cid``: its own parked slot if one exists,
        else a free slot, else the LRU parked slot (``on_evict`` is told
        which context lost its cached state)."""
        assert cid not in self.held, f"ctx {cid} already holds a slot"
        if cid in self.idle:
            slot = self.idle.pop(cid)
        elif self._free:
            slot = self._free.pop()
        elif self.idle:
            victim, slot = self.idle.popitem(last=False)
            if on_evict is not None:
                on_evict(victim)
        else:
            raise RuntimeError(
                f"all {self.n_slots} decode slots are held by in-flight "
                "generations; suspend one before switching another in")
        self.held[cid] = slot
        return slot

    def park(self, cid: int):
        """held -> idle: the generation switched out but its slot cache
        stays resident for exact reuse (MRU end of the idle order)."""
        self.idle[cid] = self.held.pop(cid)

    def release(self, cid: int):
        """Give the slot back entirely (context deleted / state reset)."""
        slot = self.held.pop(cid, None)
        if slot is None:
            slot = self.idle.pop(cid, None)
        if slot is not None:
            self._free.append(slot)


class ResidencyEngine:
    """Restore planning + chunk assembly + compress/AoT swap-out."""

    def __init__(self, exe: ModelExecutor, ctxs: ContextStore,
                 store: DiskStore, swapper: AsyncSwapper,
                 queue: LCTRUQueue, mem: MemoryManager, cfg):
        self.exe = exe
        self.ctxs = ctxs
        self.store = store
        self.swapper = swapper
        self.queue = queue
        self.mem = mem
        self.cfg = cfg
        self.slots = SlotAllocator(exe.decode_slots)
        # paged KV pool: per-context page tables replace slot-cache
        # ownership for dense families (see core/pagepool.py).  With
        # pool_persist (default) a context's pages SURVIVE switch-out —
        # the next switch-in is a page-table read; pool_persist=False is
        # the slot-like A/B baseline (pages freed at swap-out, every
        # switch-in re-admits).
        self.pool: Optional[PagePool] = (
            PagePool(exe, ctxs) if exe.paged else None)
        self.pool_persist = True
        self.profile = PipelineProfile()
        self.profiled = False
        self.epoch = 0                      # bumped on any eviction
        # multi-family routing hook (core/zoo.py): when several engines
        # share one MemoryManager/LCTRUQueue, a reclaim started by one
        # member may pick a victim chunk owned by another.  Keys whose
        # context is unknown HERE are forwarded to the owner through
        # this callable instead of being silently dropped.
        self.route_evict: Optional[Callable[[Tuple[int, int]], None]] = None
        # contexts that may hold dirty (unflushed) chunks: the §3.4
        # prediction hook flushes ONLY these instead of scanning every
        # context (the scan was O(total contexts) per completed call —
        # quadratic over a trace, and the top profile line at the scale
        # harness's 10^4 contexts).  Maintained at the single site that
        # marks chunks dirty; stale entries are dropped lazily.
        self._dirty_cids: set = set()
        # A/B control for the quant-resident tier: with the flag set,
        # switch-in MATERIALIZES every quant payload into the bf16 slot
        # (full-dequant baseline) instead of scattering codes behind the
        # fused kernel.  Payload creation is unaffected, so the two legs
        # decode from identical quantized representations — the
        # token-identity contract benchmarks/tests rely on.
        self.force_dequant = False
        # -- fault tolerance (DESIGN.md §6) ---------------------------- #
        # recovery ladder: retry (AsyncSwapper) -> recompute (here) ->
        # degrade (ENOSPC) -> fail.  While degraded, AoT swap-out is off
        # and eviction DROPS dirty payloads instead of persisting them;
        # a periodic probe write exits the mode once space returns.
        # degraded-mode flags and recovery counters are written from
        # BOTH the dispatcher and the swapper's IO threads (terminal
        # job failures land via on_job_error): every write goes through
        # _flags_lock.  Reads of the two mode FLAGS stay lock-free by
        # design (monotonic-latch pattern — see the shared-state
        # allowlist in repro/analysis/config.py).
        self._flags_lock = witness_lock("residency.flags")
        self.aot_enabled = True
        self.degraded = False
        self.degraded_entries = 0
        self.degraded_exits = 0
        self._degrade_ticks = 0
        self.chunks_recovered_recompute = 0
        self.chunks_corrupt_detected = 0
        self.io_errors_detected = 0
        self.evict_dropped = 0
        self.recover_failed = 0
        self.pipelined_restores = 0         # Fig. 8 scans that completed
        swapper.on_job_error = self._on_io_error

    # ------------------------------------------------------------------ #
    # failure detection + degraded mode (DESIGN.md §6)
    # ------------------------------------------------------------------ #
    @property
    def _deadline(self) -> Optional[float]:
        """Per-swap watchdog deadline (None = wait forever)."""
        return getattr(self.cfg, "swap_deadline_s", None)

    def _fut_result(self, fut: Future):
        """Future wait under the watchdog: a wedged swap surfaces as
        SwapTimeoutError (which the router turns into a preemption)
        instead of blocking the engine forever."""
        try:
            return fut.result(self._deadline)
        except _FutTimeout:
            raise SwapTimeoutError(
                f"swap read exceeded {self._deadline}s") from None

    def _note_read_failure(self, err: BaseException):
        with self._flags_lock:
            if isinstance(err, ChunkCorruptError):
                self.chunks_corrupt_detected += 1
            else:
                self.io_errors_detected += 1

    def _on_io_error(self, key, err: BaseException):
        """AsyncSwapper terminal-failure callback (runs on an I/O
        thread).  ENOSPC flips degraded mode immediately; every other
        failed job is recovered lazily — the next read of the key
        retries and then recomputes."""
        if isinstance(err, OSError) and err.errno == errno.ENOSPC:
            self._enter_degraded()

    def _enter_degraded(self):
        with self._flags_lock:
            if not self.degraded:
                self.degraded = True
                self.aot_enabled = False
                self.degraded_entries += 1
                self._degrade_ticks = 0

    @requires_serialized
    def degraded_tick(self):
        """Deterministic disk-space probe: every 4th switch-out while
        degraded, attempt a tiny write.  Success means space returned —
        re-enable AoT and flush what accumulated dirty in the interim.
        Tick-count based (not wall clock) so virtual-clock scenario runs
        replay identically."""
        with self._flags_lock:
            if not self.degraded:
                return
            self._degrade_ticks += 1
            if self._degrade_ticks % 4:
                return
        # probe OUTSIDE _flags_lock: the write is real (blocking) disk
        # IO and must not stall an IO thread reporting a failure
        probe = (-3, "probe")
        try:
            self.store.write(probe, b"ok")
            self.store.delete(probe)
        except OSError:
            return
        with self._flags_lock:
            self.degraded = False
            self.aot_enabled = True
            self.degraded_exits += 1
        if self.cfg.use_disk and self.cfg.chunked:
            for cid in sorted(self._dirty_cids):
                ctx = self.ctxs.contexts.get(cid)
                if ctx is not None:
                    self.flush_dirty(ctx)

    def fault_stats(self) -> Dict[str, Any]:
        c = FAULTS.counters()
        return {
            "degraded_mode": int(self.degraded),
            "degraded_entries": self.degraded_entries,
            "degraded_exits": self.degraded_exits,
            "chunks_recovered_recompute": self.chunks_recovered_recompute,
            "chunks_corrupt_detected": self.chunks_corrupt_detected,
            "io_errors_detected": self.io_errors_detected,
            "evict_dropped": self.evict_dropped,
            "recover_failed": self.recover_failed,
            "pipelined_restores": self.pipelined_restores,
            "io_retries": self.swapper.io_retries,
            "io_recovered": self.swapper.io_recovered,
            "io_failed_jobs": self.swapper.io_failed,
            "tmp_files_swept": self.store.tmp_swept,
            "delete_errors": self.store.delete_errors,
            "faults_injected_total": c["injected_total"],
            "faults_injected": c["injected"],
        }

    # ------------------------------------------------------------------ #
    # switch-in: restore every chunk to memory (Load primitive)
    # ------------------------------------------------------------------ #
    @requires_serialized
    def switch_in(self, ctx: Context):
        """-> (cache, switch_seconds).  Missing-chunk restore (reclaim +
        I/O + recompute) is the timed QoS path; resident-chunk assembly
        into the bf16 working cache is not (see LLMService.callLLM)."""
        exe = self.exe
        if self.pool is not None:
            return self._switch_in_paged(ctx)
        cache = exe.fresh_cache(ctx.n_tokens)
        if ctx.n_tokens == 0:
            return cache, 0.0
        if not self.cfg.chunked or not exe.chunked_cache:
            # whole-state families (constant-size recurrent caches)
            # degenerate to snapshot/restore regardless of policy
            return self._restore_whole_timed(ctx, cache)

        # ---- assembly of resident chunks (inference-side cost) -------- #
        # quant mode: compressed chunks go BEHIND the fused kernel —
        # decode-grid payloads scatter their codes verbatim (a pure
        # memcpy, the QUANT_RESIDENT no-op switch-in), packed 4/2-bit
        # payloads unpack + re-grid to int8; only bf16-raw (16-bit)
        # chunks still materialize in the bf16 window
        quant_mode = self.exe.quant_resident and not self.force_dequant
        by_bits: Dict[int, List[int]] = {}
        q_idxs: List[int] = []
        for i, m in sorted(ctx.chunks.items()):
            if m.in_memory:
                if quant_mode and m.bits != 16:
                    q_idxs.append(i)
                else:
                    by_bits.setdefault(m.bits, []).append(i)
                self.queue.touch((ctx.cid, i), m.bits)
                m.last_access = time.time()
        # slot-path quant assembly (paged_pool=False only; the pool
        # admits quant pages once instead): scatter each decode-grid
        # payload's codes + scales behind the fused kernel, re-gridding
        # packed 4/2-bit payloads to int8 via the qmemo
        if q_idxs:
            codec = exe.codec
            head_dims = {n: exe.work_cache[n].shape[-1]
                         for n in codec.leaves}
            codes = {n: [] for n in codec.leaves}
            scales = {n: [] for n in codec.leaves}
            for i in q_idxs:
                cc = ctx.payload[i]
                if not isinstance(cc, QuantResidentChunk):
                    cc = ctx.qmemo.get(i)
                    if cc is None:      # re-grid once per (re-)encode
                        cc = codec.quantize_resident_blocks(
                            self._payload_blocks(ctx.payload[i]), head_dims)
                        ctx.qmemo[i] = cc
                for n in codec.leaves:
                    codes[n].append(cc.data[n][0])
                    scales[n].append(cc.data[n][1])
            pos = exe.chunk_positions(q_idxs)
            pos_b = exe.bucket_pad(pos, exe.pad_slot)
            pad = len(pos_b) - len(pos)

            def assemble(parts):
                # payloads are host numpy: concatenate + pad on the host
                # and ship ONE array per leaf, ONE scatter for the whole
                # quant tier (per-chunk dispatches would dominate the
                # QoS path, and jnp.concatenate would compile a kernel
                # per (chunk-count, pad) combination)
                out = np.concatenate([np.asarray(p) for p in parts])
                if pad:
                    out = np.concatenate(
                        [out, np.zeros((pad,) + out.shape[1:], out.dtype)])
                return jnp.asarray(out)

            cache = exe.scatter_quant_fn(
                cache, jnp.asarray(pos_b),
                {n: assemble(codes[n]) for n in codec.leaves},
                {n: assemble(scales[n]) for n in codec.leaves})
        for bits, idxs in by_bits.items():
            # decode each payload once, not once per leaf
            chunk_blocks = [self._payload_blocks(ctx.payload[i])
                            for i in idxs]
            blocks = {name: jnp.concatenate(
                [cb[name] for cb in chunk_blocks])
                for name in exe.codec.leaves}
            pos = exe.chunk_positions(idxs)
            pos_b = exe.bucket_pad(pos, exe.pad_slot)
            if len(pos_b) != len(pos):
                pad = len(pos_b) - len(pos)
                blocks = {k: jnp.concatenate(
                    [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
                    for k, v in blocks.items()}
            cache = exe.scatter_fn(cache, jnp.asarray(pos_b), blocks)
        jax.block_until_ready(cache[exe.codec.leaves[0]])

        # ---- timed: reclaim + restore of missing chunks ---------------- #
        t0 = time.perf_counter()
        missing = sorted(i for i, m in ctx.chunks.items() if not m.in_memory)
        need = sum(ctx.chunks[i].nbytes for i in missing)
        self.mem.reclaim(need, self.evict, locked={ctx.cid})
        if missing:
            re_idx, io_idx = self._plan_restore(ctx, missing)
            cache = self._restore_chunks(ctx, cache, re_idx, io_idx)
            jax.block_until_ready(cache[exe.codec.leaves[0]])
        return cache, time.perf_counter() - t0

    # ------------------------------------------------------------------ #
    # paged switch-in: a page-table read plus first-admission faults
    # ------------------------------------------------------------------ #
    @requires_serialized
    def _switch_in_paged(self, ctx: Context) -> Tuple[None, float]:
        """Pool-mode switch-in.  Chunks whose pages survive from a
        previous residency cost NOTHING (their table entries are read at
        decode time); in-memory chunks without pages are admitted once
        (the page fault — ``codec``-layout payload -> page arena);
        missing chunks are restored from disk first (the timed QoS
        path).  Returns (None, t): there is no per-slot cache — the
        decode entry gathers straight from the pool."""
        exe, pool = self.exe, self.pool
        pool.table(ctx.cid)
        pool.touch(ctx.cid)
        if ctx.n_tokens == 0:
            return None, 0.0
        quant_mode = exe.quant_resident and not self.force_dequant

        # ---- untimed: resident chunks (table read / first admission) -- #
        admitted = 0
        for i, m in sorted(ctx.chunks.items()):
            if m.in_memory:
                if pool.kind(ctx.cid, i) == 0:
                    self._admit_chunk(ctx, i, quant_mode)
                    admitted += 1
                else:
                    pool.pt_switch_ins += 1
                self.queue.touch((ctx.cid, i), m.bits)
                m.last_access = time.time()
        pool.admit_switch_ins += admitted

        # ---- timed: reclaim + disk restore of missing chunks ---------- #
        t0 = time.perf_counter()
        missing = sorted(i for i, m in ctx.chunks.items() if not m.in_memory)
        if missing:
            need = sum(ctx.chunks[i].nbytes for i in missing)
            self.mem.reclaim(need, self.evict, locked={ctx.cid})
            # I/O-first restore: eviction normally persists a chunk
            # before it leaves memory, so the payload bytes exist on
            # disk — except after a storage fault (failed write, corrupt
            # file, degraded-mode drop), where the recovery ladder
            # recomputes the chunk from its tokens in ascending order
            # (each recompute attends the already-restored prefix).
            # The layer-pipelined recompute stays a slot-mode feature.
            futs = {i: self._read_chunk_async((ctx.cid, i))
                    for i in missing if ctx.chunks[i].on_disk}
            for i in missing:
                cc = None
                if i in futs:
                    try:
                        cc = self._fut_result(futs[i])
                    except SwapTimeoutError:
                        raise
                    except (ChunkCorruptError, OSError) as err:
                        self._note_read_failure(err)
                if cc is not None:
                    self._mark_loaded(ctx, i, payload=cc)
                    # a surviving page (evicted-while-busy chunk) already
                    # holds exactly this payload's values — skip the admit
                    if pool.kind(ctx.cid, i) == 0:
                        self._admit_chunk(ctx, i, quant_mode)
                else:
                    self._recover_chunk_paged(ctx, i, quant_mode)
        if admitted or missing:
            jax.block_until_ready(
                pool.arenas[exe.codec.leaves[0] + "16"])
        return None, time.perf_counter() - t0

    def _admit_chunk(self, ctx: Context, i: int, quant_mode: bool):
        """Page-fault one in-memory chunk into the pool.  Full
        decode-grid chunks take a QUANT page (codes + scales attended in
        place); everything else — bf16-raw, packed 4/2-bit, and partial
        tail chunks — dequantizes into a BF16 page.  The dequant math is
        the same fused-select arithmetic, so both kinds yield the exact
        values the slot path would attend."""
        exe, pool, codec = self.exe, self.pool, self.exe.codec
        m = ctx.chunks[i]
        cc = ctx.payload[i]
        if quant_mode and m.bits != 16 and m.n_covered == exe.cs:
            qc = cc
            if not isinstance(qc, QuantResidentChunk):
                qc = ctx.qmemo.get(i)
                if qc is None:
                    head_dims = {n: exe.work_cache[n].shape[-1]
                                 for n in codec.leaves}
                    qc = codec.quantize_resident_blocks(
                        self._payload_blocks(cc), head_dims)
                    ctx.qmemo[i] = qc
            page = pool.alloc8(ctx.cid, i)
            pool.arenas = exe.admit8_fn(
                pool.arenas, page,
                {n: jnp.asarray(qc.data[n][0]) for n in codec.leaves},
                {n: jnp.asarray(qc.data[n][1]) for n in codec.leaves})
        else:
            blocks = self._payload_blocks(cc)
            page = pool.alloc16(ctx.cid, i)
            pool.arenas = exe.admit16_fn(pool.arenas, page, blocks)
        pool.page_faults += 1

    def ensure_extend_range(self, ctx: Context, c_lo: int, c_hi: int):
        """Give chunks [c_lo, c_hi] writable bf16 pages ahead of a paged
        prefill-append.  Fresh tail chunks get pages straight off the
        free list (their garbage is never attended until written);
        anything already admitted as a quant page is converted back to
        bf16 — append must be able to write into the chunk."""
        pool = self.pool
        for ci in range(c_lo, c_hi + 1):
            k = pool.kind(ctx.cid, ci)
            if k == BF16:
                continue
            if k == QUANT or ci in ctx.payload:
                blocks = self._payload_blocks(ctx.payload[ci])
                pool.free_chunk(ctx.cid, ci)
                page = pool.alloc16(ctx.cid, ci)
                pool.arenas = self.exe.admit16_fn(pool.arenas, page, blocks)
                pool.page_faults += 1
            else:
                self._alloc_fresh16(ctx.cid, ci)

    def ensure_tail(self, ctx: Context, ci: int):
        """Give the decode tail chunk a writable bf16 page."""
        if self.pool.kind(ctx.cid, ci) == 0:
            self._alloc_fresh16(ctx.cid, ci)

    def _alloc_fresh16(self, cid: int, ci: int):
        """Allocate AND zero a fresh bf16 page: recycled pages hold
        their previous owner's data, but the slot path's never-written
        positions are exactly zero — and some of them are attended (and
        encoded at swap-out), so both paths must agree there."""
        page = self.pool.alloc16(cid, ci)
        self.pool.arenas = self.exe.zero16_fn(self.pool.arenas, page)

    # -- recompute-based recovery (ladder step 2, DESIGN.md §6) -------- #
    @staticmethod
    def _hole_segments(ctx: Context, lo: int, hi: int
                       ) -> List[Tuple[int, int]]:
        """Token ranges of [lo, hi) between KV holes.  Hole positions
        (each call's final emitted token) were never fed through the
        model, so recompute must skip them — their KV rows stay zero,
        exactly what the canonical payload stores."""
        segs, a = [], lo
        for h in sorted(x for x in ctx.kv_holes if lo <= x < hi):
            if h > a:
                segs.append((a, h))
            a = h + 1
        if hi > a:
            segs.append((a, hi))
        return segs

    def _recompute_blocks_paged(self, ctx: Context, i: int):
        """Recompute chunk ``i``'s KV into a fresh zeroed bf16 page from
        the context's resident tokens (paper §3.3: a KV chunk is always
        recomputable) and read it back as (cs, F) blocks.  Requires
        every earlier chunk's page to be resident — callers restore in
        ascending chunk order, so the prefix is always attended."""
        exe, pool = self.exe, self.pool
        m = ctx.chunks[i]
        cs = exe.cs
        lo = i * cs
        covered = m.n_covered or min(ctx.n_tokens - lo, cs)
        if pool.kind(ctx.cid, i) != 0:
            pool.free_chunk(ctx.cid, i)
        self._alloc_fresh16(ctx.cid, i)
        pt16, pt8, qmask = pool.rows([ctx.cid])
        for a, b in self._hole_segments(ctx, lo, lo + covered):
            toks = np.asarray(ctx.tokens[a:b], np.int32)
            pool.arenas, _, _ = exe.paged_extend(pool.arenas, toks, a,
                                                 pt16, pt8, qmask)
        page = int(pool._tables[ctx.cid]["p16"][i])
        return exe.read16_fn(pool.arenas, page)

    @requires_serialized
    def _recover_chunk_paged(self, ctx: Context, i: int, quant_mode: bool):
        """The disk copy is missing/corrupt/unreadable after retries:
        recompute the chunk from tokens, re-encode it at its assigned
        level, re-admit FROM THE PAYLOAD (so decode attends exactly the
        payload-roundtrip values a disk restore would have given), and
        rewrite the repaired payload to disk unless degraded."""
        if not self.exe.recomputable:
            with self._flags_lock:
                self.recover_failed += 1
            raise ChunkCorruptError(
                f"ctx {ctx.cid} chunk {i}: disk copy unreadable and "
                f"family {self.exe.model.cfg.family!r} cannot recompute")
        m = ctx.chunks[i]
        if self.pool.kind(ctx.cid, i) == BF16:
            # the page survived the eviction (busy context): it holds
            # the authoritative values — rebuild the payload from it
            # instead of recomputing
            page = int(self.pool._tables[ctx.cid]["p16"][i])
            blocks = self.exe.read16_fn(self.pool.arenas, page)
        else:
            blocks = self._recompute_blocks_paged(ctx, i)
        want_quant = self.exe.quant_resident and m.bits == 8
        cc = self._encode_blocks(blocks, m.bits, quant=want_quant)
        ctx.payload[i] = cc
        ctx.qmemo.pop(i, None)
        m.quant = want_quant
        m.nbytes = cc.nbytes
        m.in_memory = True
        # drop the raw recompute page and re-admit from the payload —
        # same drop-on-encode rule as swap-out (re-encoding is lossy for
        # quantized tiers; for 16-bit storage the roundtrip is exact)
        self.pool.free_chunk(ctx.cid, i)
        if (self.cfg.use_disk and self.aot_enabled
                and self._write_chunk_async(ctx.cid, i, cc)):
            m.dirty, m.on_disk = False, True
        else:
            m.dirty, m.on_disk = True, False
            self._dirty_cids.add(ctx.cid)
        self.mem.register((ctx.cid, i), m.nbytes, m.bits)
        self._admit_chunk(ctx, i, quant_mode)
        with self._flags_lock:
            self.chunks_recovered_recompute += 1

    def _plan_restore(self, ctx, missing: List[int]
                      ) -> Tuple[List[int], List[int]]:
        if not (self.cfg.use_pipeline and self.exe.recomputable):
            return [], missing
        plan_in = [(i, ctx.chunks[i].nbytes, True) for i in missing]
        if self.profiled:
            re_idx, io_idx, _ = plan_split(plan_in, self.profile, True)
        else:   # unprofiled fallback: split heaviest half to recompute
            order = sorted(missing, key=lambda i: -ctx.chunks[i].nbytes)
            re_idx = order[:len(order) // 2]
            io_idx = [i for i in missing if i not in set(re_idx)]
        return sorted(re_idx), sorted(io_idx)

    @requires_serialized
    def _restore_chunks(self, ctx: Context, cache, re_idx: List[int],
                        io_idx: List[int]):
        """Fig. 8 restore.  dense + recompute-set: per-layer pipelined scan;
        otherwise: async whole-chunk reads (+ recompute second phase).

        Fault recovery (DESIGN.md §6): chunks whose disk copy is
        missing/corrupt/unreadable after retries are DEMOTED to the
        recompute set instead of failing the call — a chunk is always
        recomputable from the context's tokens (paper §3.3)."""
        exe = self.exe
        quant_mode = exe.quant_resident and not self.force_dequant
        recovered: List[int] = []            # unreadable -> recomputed
        pending_io = list(io_idx)
        did_recompute = False
        use_pipe = (bool(re_idx) and exe.spec.pipelined_restore)
        if use_pipe:
            # pre-validate the feed's files: the scan reads them deep
            # inside jax io_callbacks where a corrupt file aborts the
            # whole restore — route guaranteed-bad chunks to recompute
            ok_io: List[int] = []
            for i in pending_io:
                if not ctx.chunks[i].on_disk:    # degraded-mode drop
                    recovered.append(i)
                    continue
                try:
                    self.swapper.wait((ctx.cid, i), timeout=self._deadline)
                    verify_chunk_file(self.store._path((ctx.cid, i)))
                    ok_io.append(i)
                except SwapTimeoutError:
                    raise
                except (ChunkCorruptError, OSError) as err:
                    self._note_read_failure(err)
                    recovered.append(i)
            re_all = sorted(set(re_idx) | set(recovered))
            try:
                cache = self._restore_pipelined(ctx, cache, re_all, ok_io)
                for i in ok_io:
                    self._mark_loaded(ctx, i, payload=None)
                pending_io = []
                did_recompute = True
            except SwapTimeoutError:
                raise
            except (ChunkCorruptError, OSError) as err:
                # passed header validation but failed mid-feed (e.g. a
                # flipped byte inside a layer segment): fall back to
                # whole-file reads, which verify per-layer CRCs up front
                self._note_read_failure(err)
                pending_io = ok_io
        if pending_io:
            # async whole-chunk reads, insert as they land
            futs = {i: self._read_chunk_async((ctx.cid, i))
                    for i in pending_io if ctx.chunks[i].on_disk}
            for i in pending_io:
                cc = None
                if i in futs:
                    try:
                        cc = self._fut_result(futs[i])
                    except SwapTimeoutError:
                        raise
                    except (ChunkCorruptError, OSError) as err:
                        self._note_read_failure(err)
                if cc is None:
                    if i not in recovered:
                        recovered.append(i)
                    continue
                if quant_mode and isinstance(cc, QuantResidentChunk):
                    # decode-grid bytes go straight back behind the
                    # fused kernel — the read IS the restore
                    pos = jnp.asarray(exe.chunk_positions([i]))
                    cache = exe.scatter_quant_fn(
                        cache, pos,
                        {n: jnp.asarray(cc.data[n][0])
                         for n in exe.codec.leaves},
                        {n: jnp.asarray(cc.data[n][1])
                         for n in exe.codec.leaves})
                else:
                    cache = exe.insert_fn(cache, jnp.int32(i * exe.cs),
                                          self._payload_blocks(cc))
                self._mark_loaded(ctx, i, payload=cc)

        re_all = sorted(set(re_idx) | set(recovered))
        if re_all and not did_recompute:
            if recovered and not exe.recomputable:
                with self._flags_lock:
                    self.recover_failed += 1
                raise ChunkCorruptError(
                    f"ctx {ctx.cid} chunks {recovered}: disk copies "
                    f"unreadable and family "
                    f"{exe.model.cfg.family!r} cannot recompute")
            # second phase (exact: I/O chunks now resident)
            miss_pos = self._feed_positions(ctx, re_all)
            miss_b = exe.bucket_pad(miss_pos, exe.pad_slot)
            toks_b = exe.bucket_pad(ctx.tokens[miss_pos], 0)
            cache, _, _ = exe.extend_nod_fn(
                exe.params, jnp.asarray(toks_b)[None],
                jnp.asarray(miss_b), cache, jnp.int32(ctx.n_tokens))

        # recomputed chunks: re-encode each payload at its assigned level
        rec = set(recovered)
        for i in re_all:
            m = ctx.chunks[i]
            want_quant = self.exe.quant_resident and m.bits == 8
            ctx.payload[i] = self._make_payload(cache, i, m.bits,
                                                quant=want_quant)
            ctx.qmemo.pop(i, None)
            m.quant = want_quant
            m.in_memory = True
            if i in rec:
                m.nbytes = ctx.payload[i].nbytes
                # rewrite the repaired chunk so the next restore is a
                # plain read again (unless writes are failing: leave it
                # dirty for the post-degraded flush)
                if (self.cfg.use_disk and self.aot_enabled
                        and self._write_chunk_async(ctx.cid, i,
                                                    ctx.payload[i])):
                    m.dirty, m.on_disk = False, True
                else:
                    m.dirty, m.on_disk = True, False
                    self._dirty_cids.add(ctx.cid)
                with self._flags_lock:
                    self.chunks_recovered_recompute += 1
            else:
                m.dirty = False               # already on disk
            self.mem.register((ctx.cid, i), m.nbytes, m.bits)
        return cache

    def _restore_pipelined(self, ctx: Context, cache, re_idx: List[int],
                           io_idx: List[int]):
        """The Fig. 8 layer-pipelined scan over a validated I/O set."""
        exe = self.exe
        nio_b = next(x for x in exe.io_buckets
                     if x >= max(len(io_idx), 1))
        pad_chunks = nio_b - len(io_idx)
        io_pos_b = np.concatenate(
            [exe.chunk_positions(io_idx),
             np.full(pad_chunks * exe.cs, exe.pad_slot, np.int32)])
        paths = [self.store._path((ctx.cid, i)) for i in io_idx]
        feed = LayerFeed(paths, exe.codec.leaves, exe.n_layers,
                         exe.cs, exe.leaf_dims, pad_chunks=pad_chunks,
                         pool=self.swapper.pool)
        miss_pos = self._feed_positions(ctx, re_idx)
        miss_b = exe.bucket_pad(miss_pos, exe.pad_slot)
        toks_b = exe.bucket_pad(ctx.tokens[miss_pos], 0)
        try:
            out = exe.run_pipelined(feed, toks_b, miss_b, io_pos_b,
                                    cache, ctx.n_tokens)
            jax.block_until_ready(out[exe.codec.leaves[0]])
        except BaseException as err:
            feed.close(raise_errors=False)
            # a storage fault the feed recorded reaches the caller as
            # itself (the caller falls back to whole-file reads); any
            # other failure of the scan propagates unchanged
            if isinstance(feed.error, (ChunkCorruptError, OSError)):
                raise feed.error from err
            raise
        feed.close()
        with self._flags_lock:
            self.pipelined_restores += 1
        return out

    def _feed_positions(self, ctx: Context, idxs: List[int]) -> np.ndarray:
        """Chunk positions to FEED through recompute: every position of
        the given chunks except KV holes (each call's final emitted
        token) — the original timeline never ran those through the
        model, so their cache rows stay zero, exactly what the canonical
        payload stores (see ``_make_payload_paged``)."""
        pos = self.exe.chunk_positions(idxs)
        if not ctx.kv_holes:
            return pos
        keep = np.asarray([p for p in pos if int(p) not in ctx.kv_holes],
                          np.int32)
        return keep if len(keep) else pos[:0]

    def _read_chunk_async(self, key):
        """Read a chunk file on the I/O pool, ORDERED AFTER any
        in-flight same-key AoT write: ``flush_dirty`` marks ``on_disk``
        when it SUBMITS the write, so reading the path directly races
        the writer's ``os.replace`` (FileNotFoundError under load)."""
        return self.swapper.submit(key, read_chunk_file,
                                   self.store._path(key))

    def _read_chunk(self, key):
        """Synchronous chunk-file read; blocks the caller on any
        in-flight same-key write first (see ``_read_chunk_async``),
        bounded by the watchdog deadline, with the worker retry budget
        for transient IO errors."""
        self.swapper.wait(key, timeout=self._deadline)

        def _on_retry(_k, _e):
            self.swapper.note_retry()

        return with_retries(lambda: read_chunk_file(self.store._path(key)),
                            attempts=self.swapper.retries,
                            base_s=self.swapper.retry_base_s,
                            on_retry=_on_retry)

    @requires_serialized
    def _mark_loaded(self, ctx, i: int, payload):
        if payload is None:
            payload = self._read_chunk((ctx.cid, i))
        ctx.payload[i] = payload
        ctx.qmemo.pop(i, None)
        m = ctx.chunks[i]
        m.in_memory, m.dirty = True, False
        m.quant = isinstance(payload, QuantResidentChunk)
        self.mem.register((ctx.cid, i), m.nbytes, m.bits)

    # -- whole-context policies (swap / lmk) ----------------------------- #
    @requires_serialized
    def _restore_whole_timed(self, ctx: Context, cache):
        exe = self.exe
        t_switch = 0.0
        if ctx.whole is None and self.cfg.use_disk and \
                self.store.nbytes((ctx.cid, -1)):
            t0 = time.perf_counter()
            self.mem.reclaim(self.store.nbytes((ctx.cid, -1)) or 0,
                             self.evict, locked={ctx.cid})
            try:
                ctx.whole = self.swapper.read((ctx.cid, -1),
                                              timeout=self._deadline)
                t_switch = time.perf_counter() - t0
                ctx.whole_tokens = ctx.n_tokens
                self.mem.register((ctx.cid, -1),
                                  self._whole_bytes(ctx), 16)
                self.queue.touch((ctx.cid, -1), 16)
            except SwapTimeoutError:
                raise
            except (ChunkCorruptError, OSError) as err:
                # unreadable whole-state file: drop the stale accounting
                # entry and fall through to the LMK recompute branch —
                # the whole context rebuilds from its resident text
                self._note_read_failure(err)
                self.store.drop_bytes((ctx.cid, -1))
                with self._flags_lock:
                    self.chunks_recovered_recompute += 1
        if ctx.whole is not None:
            pass                                       # resident
        else:
            # LMK: killed — recompute the whole context from its text
            t0 = time.perf_counter()
            self.mem.reclaim(0, self.evict, locked={ctx.cid})
            pos = np.arange(ctx.n_tokens, dtype=np.int32)
            if exe.pad_safe:
                pos_b = exe.bucket_pad(pos, exe.pad_slot)
                toks_b = exe.bucket_pad(ctx.tokens[:ctx.n_tokens], 0)
            else:
                # recurrent carry: pads would fold into the state
                pos_b, toks_b = pos, ctx.tokens[:ctx.n_tokens]
            cache, _, dens = exe.extend_fn(
                exe.params, jnp.asarray(toks_b)[None], jnp.asarray(pos_b),
                exe.setpos_fn(cache, jnp.int32(0)), jnp.int32(ctx.n_tokens))
            jax.block_until_ready(cache[exe.codec.leaves[0]])
            t_switch = time.perf_counter() - t0
            self.ctxs.acc_density(ctx, np.asarray(dens[0], np.float64),
                                  ctx.n_tokens)
            ctx.whole = self._extract_whole(cache, ctx.n_tokens)
            ctx.whole_tokens = ctx.n_tokens
            ctx.alive = True
            self.mem.register((ctx.cid, -1), self._whole_bytes(ctx), 16)
            return (exe.setpos_fn(cache, jnp.int32(ctx.n_tokens)), t_switch)
        blocks = {k: jnp.asarray(v) for k, v in ctx.whole.items()}
        cache = exe.insert_fn(cache, jnp.int32(0), blocks)
        self.queue.touch((ctx.cid, -1), 16)
        return exe.setpos_fn(cache, jnp.int32(ctx.n_tokens)), t_switch

    def _extract_whole(self, cache, n_tokens: int) -> Dict[str, np.ndarray]:
        hi = self.exe.bucket_len(max(n_tokens, 1))
        out = {}
        for k, v in self.exe.codec.extract(cache, 0, hi).items():
            # 16-bit floats snapshot as fp16; fp32 state stays exact —
            # rwkv6's wkv recurrence is fp32 by design, and halving it
            # would perturb every continued decode
            dt = np.float32 if v.dtype == jnp.float32 else np.float16
            out[k] = np.asarray(v, dt)
        return out

    def _whole_bytes(self, ctx) -> int:
        return sum(v.nbytes for v in (ctx.whole or {}).values())

    # -- payload codecs ------------------------------------------------- #
    def _payload_blocks(self, cc) -> Dict[str, jax.Array]:
        if isinstance(cc, QuantResidentChunk):
            return self.exe.codec.dequantize_resident(cc)
        if cc.bits == 16:
            return {k: jnp.asarray(p).astype(jnp.bfloat16)
                    for k, (p, _) in cc.data.items()}
        return self.exe.codec.decompress(cc)

    def _encode_blocks(self, blocks, bits: int, quant: bool):
        """(T, F) blocks -> payload: decode-grid QuantResidentChunk when
        ``quant``, else the storage codec at ``bits``."""
        codec = self.exe.codec
        if quant:
            head_dims = {n: self.exe.work_cache[n].shape[-1]
                         for n in codec.leaves}
            return codec.quantize_resident_blocks(blocks, head_dims)
        if bits == 16:
            return CompressedChunk(
                bits=16, n_tokens=next(iter(blocks.values())).shape[0],
                data={k: (np.asarray(v, np.float16), np.zeros(0, np.float32))
                      for k, v in blocks.items()},
                shapes={k: tuple(v.shape) for k, v in blocks.items()})
        return codec.compress_blocks(blocks, bits)

    def _make_payload(self, cache, i: int, bits: int, quant: bool = False):
        """Encode chunk i from the slot cache.  A mixed cache is read
        through ``extract_mixed`` — its bf16 array is stale at
        quant-resident positions."""
        cs = self.exe.cs
        lo, hi = i * cs, (i + 1) * cs
        codec = self.exe.codec
        blocks = (codec.extract_mixed(cache, lo, hi)
                  if self.exe.quant_resident
                  else codec.extract(cache, lo, hi))
        return self._encode_blocks(blocks, bits, quant)

    def _make_payload_paged(self, ctx: Context, i: int, bits: int,
                            quant: bool = False):
        """Encode chunk i from the pool.  A bf16 page is read back
        through the jitted page reader; a quant page (or an unadmitted
        chunk) re-encodes from its existing payload — the page holds
        exactly the payload's codes, so nothing is lost."""
        exe, pool = self.exe, self.pool
        if pool.kind(ctx.cid, i) == BF16:
            page = int(pool._tables[ctx.cid]["p16"][i])
            blocks = exe.read16_fn(pool.arenas, page)
        else:
            cc = ctx.payload.get(i)
            if cc is not None:
                blocks = self._payload_blocks(cc)
            elif ctx.chunks[i].on_disk:
                # evicted out from under a busy context by another
                # context's reclaim — eviction wrote it to disk first
                # (possibly asynchronously, via an earlier AoT flush)
                blocks = self._payload_blocks(
                    self._read_chunk((ctx.cid, i)))
            else:
                # the chunk was never written at all: its only tokens
                # are emitted-but-never-decoded (the call's final token
                # has no decode round).  The slot path encodes the zero
                # cache here — match it exactly.
                blocks = {n: jnp.zeros(
                    (exe.cs, int(np.prod(
                        [s for a, s in enumerate(exe.leaf_shapes[n])
                         if a != 2]))), jnp.bfloat16)
                    for n in exe.codec.leaves}
        return self._encode_blocks(blocks, bits, quant)

    # ------------------------------------------------------------------ #
    # compress + AoT swap-out (Reclaim is then free)
    # ------------------------------------------------------------------ #
    @requires_serialized
    def compress_and_swap_out(self, ctx: Context, cache):
        cfg = self.cfg
        if not cfg.chunked or not self.exe.chunked_cache:
            ctx.whole = self._extract_whole(cache, ctx.n_tokens)
            ctx.whole_tokens = ctx.n_tokens
            self.mem.register((ctx.cid, -1), self._whole_bytes(ctx), 16)
            return

        cs = self.exe.cs
        n_chunks = math.ceil(ctx.n_tokens / cs)
        if cfg.compression == "tolerance":
            D = comp.chunk_density(ctx.density_sum, ctx.density_cnt,
                                   ctx.n_tokens, cs)
            bits = comp.plan_buckets(D, cfg.ratio_global, cfg.levels)
        elif cfg.compression == "static8":
            D = np.zeros(n_chunks)
            bits = np.full(n_chunks, 8, np.int64)
        else:
            D = np.zeros(n_chunks)
            bits = np.full(n_chunks, 16, np.int64)
        # the family's Eq.-3 floor: MLA latents / VLM image chunks carry
        # no cross-head redundancy, so the planner never drops them
        # below KVSpec.min_bits however low their measured density
        bits = np.maximum(bits, self.exe.spec.min_bits)

        for i in range(n_chunks):
            m = ctx.chunks.get(i)
            if m is None:
                m = ChunkMeta(idx=i)
                ctx.chunks[i] = m
            want = int(bits[i])
            # §3.2 Eq. 3 bucket -> residency representation: in quant
            # mode an 8-bit chunk is PROMOTED to the decode grid (its
            # payload becomes directly decodable; switch-in degenerates
            # to a memcpy); 4/2-bit chunks keep the packed storage
            # codec — still charged at packed size — and are re-gridded
            # behind the fused kernel at assembly time
            want_quant = self.exe.quant_resident and want == 8
            m.density = float(D[i])
            covered = min(ctx.n_tokens - i * cs, cs)
            if (m.dirty or want != m.bits or i not in ctx.payload
                    or covered != m.n_covered or m.quant != want_quant):
                if self.pool is not None:
                    try:
                        cc = self._make_payload_paged(ctx, i, want,
                                                      quant=want_quant)
                    except (ChunkCorruptError, OSError) as err:
                        # the encode needed the chunk's disk copy (busy-
                        # evicted, no page) and it is unreadable.  The
                        # prefix may be paged out here, so recompute is
                        # not safe mid-swap-out — leave the chunk
                        # MISSING; the next switch-in recovers it with
                        # the prefix resident (recovery ladder §6)
                        self._note_read_failure(err)
                        m.bits, m.n_covered = want, covered
                        m.density = float(D[i])
                        m.quant = want_quant
                        m.dirty, m.in_memory, m.on_disk = \
                            False, False, False
                        ctx.payload.pop(i, None)
                        ctx.qmemo.pop(i, None)
                        self.pool.free_chunk(ctx.cid, i)
                        self.mem.unregister((ctx.cid, i))
                        continue
                    # drop-on-encode: the page now disagrees with the
                    # canonical payload (re-encoding is lossy), so free
                    # it — the next switch-in re-admits from the payload
                    # and attends exactly what the slot path would
                    self.pool.free_chunk(ctx.cid, i)
                else:
                    cc = self._make_payload(cache, i, want,
                                            quant=want_quant)
                ctx.payload[i] = cc
                ctx.qmemo.pop(i, None)
                m.bits, m.nbytes, m.n_covered = want, cc.nbytes, covered
                m.quant = want_quant
                m.dirty, m.in_memory, m.on_disk = True, True, False
                self._dirty_cids.add(ctx.cid)
                # AoT re-admit (§3.4 spirit, like the qmemo re-grid
                # below): pay the page write NOW, at switch-out, so the
                # next switch-in is a pure page-table read — of exactly
                # the payload-roundtrip values the slot path would
                # scatter.  Best-effort: an exhausted pool just leaves
                # the chunk paged-out for a later switch-in fault.
                if (self.pool is not None and self.pool_persist
                        and not self.force_dequant):
                    try:
                        self._admit_chunk(ctx, i, self.exe.quant_resident)
                    except RuntimeError:
                        pass
            # AoT re-grid (§3.4 spirit): a packed 4/2-bit chunk whose
            # payload was just (re-)encoded gets its decode-grid memo
            # built NOW, at switch-out, so the next switch-in stays a
            # pure scatter.  Built from the packed payload (not the raw
            # cache) so assembly sees identical codes before and after
            # an eviction/restore round trip.
            if (self.exe.quant_resident and not m.quant and m.bits != 16
                    and i not in ctx.qmemo and i in ctx.payload):
                ctx.qmemo[i] = self.exe.codec.quantize_resident_blocks(
                    self._payload_blocks(ctx.payload[i]),
                    {n: self.exe.work_cache[n].shape[-1]
                     for n in self.exe.codec.leaves})
            self.mem.register((ctx.cid, i), m.nbytes, m.bits)
            m.last_access = time.time()

        # pool_persist=False (and the force_dequant control): behave
        # like the slot path — pages die with the residency, so every
        # switch-in pays the full re-admission
        if self.pool is not None and not (self.pool_persist
                                          and not self.force_dequant):
            self.pool.free_ctx(ctx.cid)

        if cfg.use_aot and cfg.use_disk:
            self.flush_dirty(ctx)
        self.degraded_tick()

    @requires_serialized
    def flush_dirty(self, ctx: Context) -> int:
        """AoT swap-out (§3.4): asynchronously write every dirty chunk so a
        later Reclaim is free.  Also the scheduler's prediction hook: when
        the router predicts a context switch, the outgoing contexts get
        flushed ahead of the memory pressure.  Returns chunks submitted.
        Disabled while degraded — writes are failing; chunks stay dirty
        and the post-degraded flush catches them up."""
        if not self.aot_enabled:
            return 0
        n = 0
        for i, m in ctx.chunks.items():
            if m.dirty and i in ctx.payload:
                if not self._write_chunk_async(ctx.cid, i, ctx.payload[i]):
                    break               # disk full: stop, chunks stay dirty
                m.dirty, m.on_disk = False, True
                n += 1
        if not any(m.dirty for m in ctx.chunks.values()):
            self._dirty_cids.discard(ctx.cid)
        return n

    @requires_serialized
    def prepare_switch(self, predicted_cid: int) -> int:
        """Next-context prediction hint (scheduler -> §3.4 AoT swap-out):
        protect the predicted context's resident chunks in the LCTRU order
        and flush dirty chunks of every OTHER context ahead of time.
        Returns the number of chunks flushed."""
        pred = self.ctxs.contexts.get(predicted_cid)
        if pred is not None:
            for i, m in pred.chunks.items():
                if m.in_memory:
                    self.queue.touch((pred.cid, i), m.bits)
            if pred.whole is not None:
                self.queue.touch((pred.cid, -1), 16)
        if not (self.cfg.use_disk and self.cfg.chunked):
            return 0
        flushed = 0
        # only contexts that can actually hold dirty chunks — NOT a scan
        # over every context (that was quadratic over a long trace)
        for cid in sorted(self._dirty_cids):
            if cid == predicted_cid:
                continue
            ctx = self.ctxs.contexts.get(cid)
            if ctx is None:                     # deleted since marked
                self._dirty_cids.discard(cid)
                continue
            flushed += self.flush_dirty(ctx)
        return flushed

    def _write_chunk_async(self, cid: int, idx: int,
                           cc: CompressedChunk) -> bool:
        """Submit an AoT chunk write; False when the disk is full (the
        chunk must stay dirty).  A full filesystem fails ``write()``
        immediately, so ENOSPC surfaces HERE on the submitting
        (dispatcher) thread — degraded-mode entry is then deterministic
        under the loadgen virtual clock instead of landing at whatever
        wall instant an IO worker would report it."""
        key = (cid, idx)
        if FAULTS.disk_full:
            self.swapper.note_io_failure()
            self._on_io_error(key, DiskFullError(
                f"disk full (write {key})"))
            return False
        path = self.store._path(key)

        def work():
            n = write_chunk_file(path, cc, self.exe.n_layers)
            self.store.set_bytes(key, n)
        self.swapper.submit(key, work)
        return True

    # ------------------------------------------------------------------ #
    # eviction (Reclaim primitive)
    # ------------------------------------------------------------------ #
    @requires_serialized
    def evict(self, key):
        cid, idx = key
        if self.route_evict is not None and cid not in self.ctxs.contexts:
            # shared-budget reclaim picked another family's chunk: hand
            # the key to its owning engine (which bumps ITS epoch)
            self.route_evict(key)
            return
        self.epoch += 1
        ctx = self.ctxs.contexts.get(cid)
        if ctx is None:
            return
        if idx == -1:
            if self.cfg.use_disk and ctx.whole is not None:
                try:                                     # sync: paper's
                    self.store.write((cid, -1), ctx.whole)  # reclaim-
                except OSError as err:                   # time cost
                    # can't persist: degrade on ENOSPC and drop — an
                    # older on-disk copy covers fewer tokens, so the
                    # accounting entry must go too (the next restore
                    # then recomputes from text, LMK-style)
                    if getattr(err, "errno", None) == errno.ENOSPC:
                        self._enter_degraded()
                    self.evict_dropped += 1
                    self.store.drop_bytes((cid, -1))
            ctx.whole = None
            ctx.alive = False
            return
        m = ctx.chunks.get(idx)
        if m is None:
            return
        if m.dirty:                         # no-AoT policies pay here (sync)
            ok = False
            if not self.degraded:           # degraded: every write fails
                try:
                    n = with_retries(
                        lambda: write_chunk_file(self.store._path(key),
                                                 ctx.payload[idx],
                                                 self.exe.n_layers),
                        attempts=self.swapper.retries,
                        base_s=self.swapper.retry_base_s)
                    self.store.set_bytes(key, n)
                    ok = True
                except OSError as err:
                    if getattr(err, "errno", None) == errno.ENOSPC:
                        self._enter_degraded()
            if not ok:
                # recovery ladder: the chunk stays recomputable from
                # tokens, so eviction must not wedge the reclaim path —
                # drop the payload and let the next switch-in recompute
                self.evict_dropped += 1
                m.dirty, m.on_disk, m.in_memory = False, False, False
                ctx.payload.pop(idx, None)
                ctx.qmemo.pop(idx, None)
                if self.pool is not None and not ctx.busy:
                    self.pool.free_chunk(cid, idx)
                return
            m.dirty = False
        m.on_disk, m.in_memory = True, False
        ctx.payload.pop(idx, None)
        ctx.qmemo.pop(idx, None)
        # free the chunk's pool pages too — unless the context is mid-
        # generation: a busy context's pages are its authoritative state
        # (the payload just written covers only the last swap-out), and
        # its own swap-out will re-encode + drop them
        if self.pool is not None and not ctx.busy:
            self.pool.free_chunk(cid, idx)

    # ------------------------------------------------------------------ #
    @requires_serialized
    def profile_pipeline(self, n_points: Tuple[int, ...] = (1, 2, 4)):
        """Paper §3.3.i: one-shot installation-time profiling of T_re/T_IO."""
        exe = self.exe
        if not (exe.recomputable and exe.chunked_cache):
            return          # pipeline planning is a chunk-restore notion
        toks = np.ones(exe.n_slots, np.int32)
        cache = exe.fresh_cache(0)
        xs, ts = [], []
        for x in n_points:
            M = x * exe.cs
            pos_b = exe.bucket_pad(np.arange(M, dtype=np.int32),
                                   exe.pad_slot)
            toks_b = exe.bucket_pad(toks[:M], 0)
            args = (exe.params, jnp.asarray(toks_b)[None],
                    jnp.asarray(pos_b), cache, jnp.int32(M))
            out = exe.extend_nod_fn(*args)               # compile
            jax.block_until_ready(out[0][exe.codec.leaves[0]])
            t0 = time.perf_counter()
            out = exe.extend_nod_fn(*args)
            jax.block_until_ready(out[0][exe.codec.leaves[0]])
            ts.append(time.perf_counter() - t0)
            xs.append(x)
        self.profile.re_base, self.profile.re_per_chunk = fit_linear(xs, ts)

        cc = self._make_payload(exe.work_cache, 0, 8)
        ios_x, ios_t = [], []
        for n in (1, 2, 4):
            paths = [self.store._path((-2, f"probe{j}")) for j in range(n)]
            for p in paths:
                write_chunk_file(p, cc, exe.n_layers)
            t0 = time.perf_counter()
            for p in paths:
                read_chunk_file(p)
            ios_t.append(time.perf_counter() - t0)
            ios_x.append(n * cc.nbytes)
            for p in paths:
                os.remove(p)
        self.profile.io_base, self.profile.io_per_byte = \
            fit_linear(ios_x, ios_t)
        self.profiled = True
