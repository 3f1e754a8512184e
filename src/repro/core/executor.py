"""Model executor — the jitted entry points of the serving stack.

Layer 1 of the four-layer design (DESIGN.md §1): owns the bf16 working
cache — ``decode_batch`` independent slot caches, so up to B contexts
are simultaneously hot — the power-of-two bucket/padding logic that
keeps jit compilation counts bounded (token buckets for prefill, batch
buckets for the batched decode entry), and the process-wide
``_JIT_CACHE`` shared across service instances of the same
(model-fingerprint, window) so benchmark sweeps don't recompile.
Everything above (residency, scheduler) treats this layer as "run the
model on these tokens/positions"; nothing here knows about
chunks-on-disk, budgets, or apps.

Every capability decision here is driven by the family's declarative
``KVSpec`` (``model.kv_spec()``): which codec slices the cache
(``ChunkCodec`` over ``spec.seq_leaves`` vs. ``WholeStateCodec`` over
``spec.state_leaves``), whether prompts may be bucket-padded
(``spec.pad_safe`` — recurrent state folds pad tokens into the carry,
so those families extend at exact length), and the
batched/quant/paged/recompute gates.  No family string dispatch.

``extend`` (prefill) and ``decode`` (one token, one slot) are the
stepwise slot-cache entry points; when the paged KV pool is enabled
(``cfg.paged_pool`` + ``spec.paged``) the ``paged_extend``/``paged_decode``
entries run the same computations directly over the global page arenas
— per-slot page-table rows gather each context's chunks into the dense
layout inside the jitted step, so batch membership changes cost a
page-table row swap instead of the merge/split copies the old BatchRun
path paid.  ``LLMService`` drives one decode round per
``decode_step``/``decode_step_batch`` so the router can slice
generations, batch compatible contexts, and preempt between slices
(DESIGN.md §2).
"""
from __future__ import annotations

import functools
import hashlib
import math
import weakref
from collections import OrderedDict
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunks import ChunkCodec, WholeStateCodec
from repro.core.telemetry import Telemetry
from repro.models.common import paged_decode_in_place
from repro.models.kvspec import LAYOUT_MIXED, LAYOUT_WINDOW

Array = jax.Array

# (model-fingerprint, window, n_sinks, family, chunk_tokens[, entry])
# -> jitted callables.  Shared process-wide so sweeps over policies /
# budgets reuse compilations.  Keys use a STABLE content fingerprint of
# (config, param treedef/shapes/dtypes) — never ``id(model)``: a dead
# model's id can be reused by a new object, which would silently hand it
# callables closing over the old model — and the cache is LRU-bounded so
# long sweeps over many distinct models can't grow it without bound.
_JIT_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
_JIT_CACHE_MAX = 64

# model object -> fingerprint memo.  Weak keys: memoizing must not keep
# retired models (and the params they map to) alive.
_FPRINT_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jit_cache_get(key):
    val = _JIT_CACHE.get(key)
    if val is not None:
        _JIT_CACHE.move_to_end(key)
    return val


def _jit_cache_put(key, val):
    _JIT_CACHE[key] = val
    _JIT_CACHE.move_to_end(key)
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)


def model_fingerprint(model, params) -> str:
    """Stable identity of the jitted computation: model class + full
    config + parameter tree structure/shapes/dtypes.  Two models with
    the same fingerprint lower to identical HLO, so sharing their cache
    entries is sound; two models that differ in any of these never
    collide (even if ``id()`` is reused after a GC)."""
    fp = _FPRINT_MEMO.get(model)
    if fp is None:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        sig = (type(model).__name__, repr(model.cfg), str(treedef),
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        fp = hashlib.sha1(repr(sig).encode()).hexdigest()
        _FPRINT_MEMO[model] = fp
    return fp


# The pipelined recompute scan pulls per-layer I/O data through an
# ordered io_callback; the active LayerFeed is published here by the
# residency engine just before dispatch (single-threaded by design —
# the scheduler serializes all model execution) and cleared when the
# dispatch completes, so no stale feed (or the chunk buffers it holds)
# outlives its restore.
_ACTIVE_FEED = None


def _feed_fetch(layer):
    return _ACTIVE_FEED.fetch(layer)


# The programs that write the page arenas donate them (their ``arenas``
# argument): the page or token rows they write land in the arena's own
# buffer instead of in a fresh copy of the whole arena.  A caller's
# handle to the arenas it passed is dead after the dispatch; every
# caller rebinds the result (``pool.arenas = exe.<entry>(pool.arenas,
# ...)``) and none may keep an older handle.  The one exception is the
# decode round on the gather path (``decode_donates``).

def decode_donates(model, quant_resident: bool = False) -> bool:
    """Whether the paged decode round donates its arenas: where it reads
    its pages in place (``paged_decode_in_place``), so that donated it
    writes the round's K/V rows into them and copies no arena.  On the
    gather path the compiler copies and relayouts the arenas into the
    dense view whether they are donated or not, and holds more
    temporaries when they are (DeepSeek-V2-Lite's round compiled for a
    v5e: 1.22 -> 1.83 GB), so that round keeps its arguments."""
    spec = model.kv_spec()
    shapes = {n + "16": jax.ShapeDtypeStruct(spec.leaf_dims[n], jnp.bfloat16)
              for n in spec.seq_leaves}
    return not quant_resident and paged_decode_in_place(shapes, None)


def paged_decode_program(model, window: int = 0, n_sinks: int = 0,
                         quant_resident: bool = False):
    """The jitted paged decode round, density on, its arenas (argument
    2) donated where ``decode_donates``.  The layer unroll mirrors the
    old batched-decode entry: XLA CPU's rolled scan shuffles the
    gathered multi-row cache every layer and dominates the step."""
    L = model.cfg.n_layers
    return jax.jit(functools.partial(
        model.decode_paged, want_density=True, unroll=L if L <= 48 else 1,
        window=window, n_sinks=n_sinks),
        donate_argnums=2 if decode_donates(model, quant_resident) else ())


def paged_extend_program(model, window: int = 0, n_sinks: int = 0):
    """The jitted paged prefill-append, density on, arenas (argument 3)
    donated."""
    return jax.jit(functools.partial(
        model.extend_paged, want_density=True, window=window,
        n_sinks=n_sinks), donate_argnums=3)


def page_programs(leaves: Tuple[str, ...], leaf_dims, cs: int,
                  n_layers: int):
    """The jitted page writes (``admit16``, ``admit8``, ``zero16``,
    arenas donated) and page read (``read16``) of a pool whose arenas
    are ``(n_layers, P, cs, *leaf_dims[n])`` per leaf ``n``."""
    dims, nl = dict(leaf_dims), n_layers

    # admission converts the chunk-file block layout (cs, L*prod(dims))
    # used by the codec/payload paths into the page layout (L, cs,
    # *dims) inside the jit, so host code hands over exactly the
    # payload blocks.
    def admit16(arenas, page, blocks):
        out = dict(arenas)
        for n in leaves:
            t = blocks[n].reshape(cs, nl, 1, *dims[n])[:, :, 0]
            t = jnp.moveaxis(t, 0, 1)
            out[n + "16"] = arenas[n + "16"].at[:, page].set(
                t.astype(arenas[n + "16"].dtype))
        return out

    def admit8(arenas, page, codes, scales):
        out = dict(arenas)
        for n in leaves:
            t = codes[n].reshape(cs, nl, 1, *dims[n])[:, :, 0]
            out[n + "8"] = arenas[n + "8"].at[:, page].set(
                jnp.moveaxis(t, 0, 1))
            s = scales[n].reshape(cs, nl, *dims[n][:-1])
            out[n + "8s"] = arenas[n + "8s"].at[:, page].set(
                jnp.moveaxis(s, 0, 1))
        return out

    def read16(arenas, page):
        return {n: jnp.moveaxis(
            arenas[n + "16"][:, page], 0, 1).reshape(cs, -1)
            for n in leaves}

    # fresh tail pages must start as zeros: the slot path's never-written
    # positions are exactly zero (fresh_cache is the shared zero cache),
    # and unwritten-but-attended positions (e.g. a call's final emitted
    # token) must encode identically on both paths
    def zero16(arenas, page):
        out = dict(arenas)
        for n in leaves:
            a = arenas[n + "16"]
            out[n + "16"] = a.at[:, page].set(
                jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype))
        return out

    return {"admit16": jax.jit(admit16, donate_argnums=0),
            "admit8": jax.jit(admit8, donate_argnums=0),
            "read16": jax.jit(read16),
            "zero16": jax.jit(zero16, donate_argnums=0)}


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


class ModelExecutor:
    """Jitted model entry points + bucket/padding helpers (one model).

    The paged entries (``paged_extend``/``paged_decode``/``admit16``/
    ``admit8``/``zero16``/``read16``) dispatch their programs inside an
    ``exe.<entry>`` span of ``telemetry``, so a profiler trace shows
    which entry issued each device program; the wait for the results
    and their copy to the host fall outside it.  The writing entries
    (all but ``read16``, and ``paged_decode`` only where
    ``decode_donates``) donate the arenas they are passed and count
    whether the program took them (``exe.arena_donated``) or wrote a
    copy (``exe.arena_copied``)."""

    def __init__(self, model, params, cfg,
                 telemetry: Optional[Telemetry] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.tel = telemetry if telemetry is not None else Telemetry()
        mc = model.cfg
        spec = model.kv_spec()
        self.spec = spec
        if not spec.servable:
            raise ValueError(
                f"family {spec.family!r} is not servable: its KVSpec "
                "declares no text-only prefill/extend entry")
        self.cs = cfg.chunk_tokens
        self.n_slots = math.ceil(cfg.max_ctx_len / self.cs) * self.cs
        self.chunked_cache = spec.chunkable
        if spec.chunkable:
            self.codec = ChunkCodec(spec.seq_leaves, self.cs)
        else:
            self.codec = WholeStateCodec(spec.state_leaves, self.cs)
        self.recomputable = spec.recomputable
        self.pad_safe = spec.pad_safe

        # quant-resident working cache: bf16 recent window + int8 chunk
        # segments the fused decode-attention kernels read in place
        self.quant_resident = bool(getattr(cfg, "quant_resident", False))
        if self.quant_resident and not spec.quant_resident:
            raise ValueError(
                f"family {spec.family!r} does not support the quant-resident "
                "working cache (families opt in via KVSpec.quant_resident)")

        # working cache: decode_batch independent slot caches (the
        # paper's working-set lock generalized to a slot table); each
        # slot is a batch-1 cache restored/switched independently.  In
        # paged mode the slots are page-table views into the pool and
        # decode runs one [B, 1] jitted step over gathered page rows.
        self.decode_slots = max(1, int(getattr(cfg, "decode_batch", 1) or 1))
        self.can_batch_decode = spec.batched_decode
        self.tok_buckets = _pow2_buckets(self.cs, self.n_slots)
        self.io_buckets = _pow2_buckets(1, max(self.n_slots // self.cs, 1))
        self.batch_buckets = _pow2_buckets(1, self.decode_slots)
        self.s_work = self.n_slots + self.tok_buckets[-1]
        self.pad_slot = self.s_work - 1
        self.work_cache = model.init_cache(
            1, self.s_work,
            layout=LAYOUT_MIXED if self.quant_resident else LAYOUT_WINDOW)
        self._zero_cache = self.work_cache

        self._fp = model_fingerprint(model, params)
        ck = (self._fp, cfg.window, cfg.n_sinks, mc.family, self.cs)
        cached = _jit_cache_get(ck)
        if cached is None:
            cw = dict(window=cfg.window, n_sinks=cfg.n_sinks)
            cached = {
                "extend": jax.jit(functools.partial(
                    model.recompute, want_density=True, **cw)),
                "extend_nod": jax.jit(functools.partial(
                    model.recompute, want_density=False, **cw)),
                "decode": jax.jit(functools.partial(
                    model.decode_step, want_density=True, **cw)),
                "logits": jax.jit(
                    lambda p, h: (h @ model.head_weight(p)
                                  ).astype(jnp.float32)),
                "insert": jax.jit(self.codec.insert),
                "scatter": jax.jit(self.codec.scatter),
                "scatter_quant": jax.jit(self.codec.scatter_quant),
                "setpos": jax.jit(lambda c, p: {**c, "pos": p}),
            }
            _jit_cache_put(ck, cached)
        self.extend_fn = cached["extend"]
        self.extend_nod_fn = cached["extend_nod"]
        self.decode_fn = cached["decode"]
        self.logits_fn = cached["logits"]
        self.insert_fn = cached["insert"]
        self.scatter_fn = cached["scatter"]
        self.scatter_quant_fn = cached["scatter_quant"]
        self.setpos_fn = cached["setpos"]

        shapes = {k: v.shape for k, v in self.work_cache.items()
                  if k in self.codec.leaves}
        self.leaf_shapes = shapes
        self.n_layers = next(iter(shapes.values()))[0]
        self.leaf_dims = dict(spec.leaf_dims)

        # paged KV pool: contexts whose spec declares ``paged`` decode
        # as views into one global page arena instead of owning slot
        # caches.  Families without the capability (recurrent state,
        # overridden decode entries) keep the slot path.
        self.paged = (
            bool(getattr(cfg, "paged_pool", False))
            and bool(getattr(cfg, "chunked", False))
            and spec.paged
            and self.can_batch_decode
            and self.s_work % self.cs == 0)
        self.pages_per_ctx = self.s_work // self.cs
        if self.paged:
            C = self.pages_per_ctx
            # +1 everywhere: page 0 is the reserved scratch page.  The
            # bf16 arena must at least fit every decode slot's full page
            # row or a single round could not be satisfied.
            self.pool_pages16 = max(
                int(getattr(cfg, "pool_pages_16", 0) or 16 * C + 1),
                self.decode_slots * C + 1)
            self.pool_pages8 = (
                int(getattr(cfg, "pool_pages_8", 0) or 16 * C + 1)
                if self.quant_resident else 1)
            pk = (self._fp, cfg.window, cfg.n_sinks, mc.family, self.cs,
                  self.quant_resident, "paged")
            pcached = _jit_cache_get(pk)
            if pcached is None:
                cw = dict(window=cfg.window, n_sinks=cfg.n_sinks)
                qr = self.quant_resident
                pcached = {
                    "decode": paged_decode_program(model, quant_resident=qr,
                                                   **cw),
                    "decode_donates": decode_donates(model, qr),
                    "extend": paged_extend_program(model, **cw),
                    **page_programs(tuple(self.codec.leaves),
                                    self.leaf_dims, self.cs, self.n_layers),
                }
                _jit_cache_put(pk, pcached)
            self.paged_decode_fn = pcached["decode"]
            self.decode_donates = pcached["decode_donates"]
            self.paged_extend_fn = pcached["extend"]
            self.admit16_fn = pcached["admit16"]
            self.admit8_fn = pcached["admit8"]
            self.read16_fn = pcached["read16"]
            self.zero16_fn = pcached["zero16"]

    @property
    def max_request_tokens(self) -> int:
        """Largest prompt+generation a single request may add: half the
        token window, so one call can never condense its own output."""
        return self.n_slots // 2

    # -- bucket / padding helpers ------------------------------------- #
    def bucket_len(self, n: int) -> int:
        return next(x for x in self.tok_buckets if x >= n)

    def bucket_pad(self, arr: np.ndarray, fill) -> np.ndarray:
        b = self.bucket_len(len(arr))
        if b == len(arr):
            return arr
        return np.concatenate([arr, np.full(b - len(arr), fill, arr.dtype)])

    def chunk_positions(self, idxs: Sequence[int]) -> np.ndarray:
        pos = []
        for i in idxs:
            pos.extend(range(i * self.cs, (i + 1) * self.cs))
        return np.asarray(pos, np.int32)

    # -- model entry points ------------------------------------------- #
    def fresh_cache(self, n_tokens: int):
        return self.setpos_fn(self._zero_cache, jnp.int32(n_tokens))

    def extend(self, cache, prompt: np.ndarray, n0: int):
        """Append ``prompt`` at positions [n0, n0+M) -> (cache, last-token
        logits, per-position density mass)."""
        M = len(prompt)
        pos = np.arange(n0, n0 + M, dtype=np.int32)
        if self.pad_safe:
            pos_b = self.bucket_pad(pos, self.pad_slot)
            toks_b = self.bucket_pad(np.asarray(prompt, np.int32), 0)
        else:
            # recurrent carry: a pad token would fold into the state —
            # run at exact length (one retrace per distinct length)
            pos_b, toks_b = pos, np.asarray(prompt, np.int32)
        cache, hidden, dens = self.extend_fn(
            self.params, jnp.asarray(toks_b)[None], jnp.asarray(pos_b),
            cache, jnp.int32(n0 + M))
        logits = np.asarray(self.logits_fn(self.params, hidden[:, M - 1]))[0]
        cache = self.setpos_fn(cache, jnp.int32(n0 + M))
        return cache, logits, np.asarray(dens[0], np.float64)

    def decode(self, cache, tok: int):
        out, mass = self.decode_fn(
            self.params, jnp.asarray([[tok]], jnp.int32), cache)
        return (out.cache, np.asarray(out.logits[0]),
                np.asarray(mass[0], np.float64))

    # -- paged KV pool entry points ----------------------------------- #
    def init_arenas(self):
        """Fresh page arenas — one fixed buffer per (leaf, kind).  Page 0
        is the reserved scratch/zero page every unowned page-table entry
        points at; its contents are garbage after the first write and
        never attended (the causal/seq-len masks zero those positions)."""
        assert self.paged
        arenas = {}
        for n in self.codec.leaves:
            dims = self.leaf_dims[n]
            arenas[n + "16"] = jnp.zeros(
                (self.n_layers, self.pool_pages16, self.cs, *dims),
                self.work_cache[n].dtype)
            if self.quant_resident:
                arenas[n + "8"] = jnp.zeros(
                    (self.n_layers, self.pool_pages8, self.cs, *dims),
                    jnp.int8)
                arenas[n + "8s"] = jnp.zeros(
                    (self.n_layers, self.pool_pages8, self.cs, *dims[:-1]),
                    jnp.float32)
        return arenas

    def paged_extend(self, arenas, prompt: np.ndarray, n0: int,
                     pt16, pt8, qmask):
        """Paged form of ``extend``: append ``prompt`` at [n0, n0+M) for
        the single context whose page-table row is ``pt16[0]`` (and
        ``pt8[0]``/``qmask[0]`` under quant_resident, else None).
        Padded positions land on the scratch page 0.
        -> (arenas', last-token logits, per-position density mass)."""
        M = len(prompt)
        pos = np.arange(n0, n0 + M, dtype=np.int32)
        pos_b = self.bucket_pad(pos, self.pad_slot)
        toks_b = self.bucket_pad(np.asarray(prompt, np.int32), 0)
        with self.tel.span("exe.paged_extend"):
            out, hidden, dens = self.paged_extend_fn(
                self.params, jnp.asarray(toks_b)[None], jnp.asarray(pos_b),
                arenas, jnp.asarray(pt16),
                None if pt8 is None else jnp.asarray(pt8),
                None if qmask is None else jnp.asarray(qmask),
                jnp.int32(n0 + M))
            self._count_donation(arenas)
            logits = self.logits_fn(self.params, hidden[:, M - 1])
            dens = dens[0]
        return out, np.asarray(logits)[0], np.asarray(dens, np.float64)

    def paged_decode(self, arenas, toks: Sequence[int], pos: Sequence[int],
                     pt16, pt8, qmask):
        """One decode round for n contexts over the pool: row i advances
        by ``toks[i]`` at its own position ``pos[i]``, batch-bucketed.
        Pad rows get the all-zero page-table row (scratch page) and are
        sliced off the outputs.  -> (arenas', logits [n, V],
        density-mass [n, S]).  Every round counts, in
        ``decode.pages_attended``, the pages its real rows' attention
        spans (``pos // cs + 1`` a row, the new token's page included);
        one that reads its pages in place also counts one
        ``decode.inplace_rounds``.  A routed model's round counts one
        ``moe.decode_rounds`` and adds to ``moe.experts_hit`` the
        distinct experts its real rows route to, summed over the routed
        layers (from the expert ids the program returns, fetched with
        the logits)."""
        n = len(toks)
        nb = next(b for b in self.batch_buckets if b >= n)
        toks_b = np.zeros((nb, 1), np.int32)
        toks_b[:n, 0] = toks
        pos_b = np.zeros(nb, np.int32)
        pos_b[:n] = pos
        C = pt16.shape[1]
        pt16_b = np.zeros((nb, C), np.int32)
        pt16_b[:n] = pt16
        pt8_b = qmask_b = None
        if pt8 is not None:
            pt8_b = np.zeros((nb, C), np.int32)
            pt8_b[:n] = pt8
            qmask_b = np.zeros((nb, C), bool)
            qmask_b[:n] = qmask
        if paged_decode_in_place(arenas, pt8):
            self.tel.count("decode.inplace_rounds")
        self.tel.count("decode.pages_attended",
                       int(sum(p // self.cs + 1 for p in pos)))
        with self.tel.span("exe.paged_decode"):
            out, *outs = self.paged_decode_fn(
                self.params, jnp.asarray(toks_b), arenas,
                jnp.asarray(pt16_b),
                None if pt8_b is None else jnp.asarray(pt8_b),
                None if qmask_b is None else jnp.asarray(qmask_b),
                jnp.asarray(pos_b))
            if self.decode_donates:
                self._count_donation(arenas)
        logits, mass, *experts = jax.device_get(outs)
        if experts:
            self.tel.count("moe.decode_rounds")
            self.tel.count("moe.experts_hit", sum(
                len(np.unique(ids[:n])) for ids in experts[0]))
        return (out, np.asarray(logits)[:n],
                np.asarray(mass, np.float64)[:n])

    def _count_donation(self, arenas):
        """After a writing program's dispatch: ``exe.arena_donated`` if
        it took the buffers of the ``arenas`` it was passed (their first
        leaf is deleted), else ``exe.arena_copied`` (the runtime refused
        the alias and the program wrote a whole new arena)."""
        leaf = next(iter(arenas.values()))
        self.tel.count("exe.arena_donated" if leaf.is_deleted()
                       else "exe.arena_copied")

    # page writes and reads of the pool (page 0 is the scratch page);
    # a write donates the arenas, so it moves one page's bytes and
    # the passed ``arenas`` are dead afterwards
    def admit16(self, arenas, page: int, blocks):
        """Write (cs, F) bf16 ``blocks`` into bf16 page ``page`` of the
        donated ``arenas``; -> the arenas, written in place."""
        with self.tel.span("exe.admit16"):
            out = self.admit16_fn(arenas, page, blocks)
            self._count_donation(arenas)
        self.tel.count("pages_admitted16")
        return out

    def admit8(self, arenas, page: int, codes, scales):
        """Write decode-grid int8 ``codes`` + ``scales`` into quant page
        ``page`` of the donated ``arenas``; -> the arenas, written in
        place."""
        with self.tel.span("exe.admit8"):
            out = self.admit8_fn(arenas, page, codes, scales)
            self._count_donation(arenas)
        self.tel.count("pages_admitted8")
        return out

    def zero16(self, arenas, page: int):
        """Zero bf16 page ``page`` of the donated ``arenas``; -> the
        arenas, written in place."""
        with self.tel.span("exe.zero16"):
            out = self.zero16_fn(arenas, page)
            self._count_donation(arenas)
        self.tel.count("pages_zeroed")
        return out

    def read16(self, arenas, page: int):
        """bf16 page ``page`` as (cs, F) blocks per leaf."""
        with self.tel.span("exe.read16"):
            return self.read16_fn(arenas, page)

    def run_pipelined(self, feed, toks_b, miss_b, io_pos_b, cache, n_total):
        """Dispatch the layer-pipelined recompute scan, with ``feed``
        published as the active per-layer I/O source for exactly the
        duration of the dispatch (cleared even on failure, so a stale
        feed can never leak into a later retrace or pin chunk buffers)."""
        global _ACTIVE_FEED
        assert _ACTIVE_FEED is None, "re-entrant pipelined restore"
        _ACTIVE_FEED = feed
        try:
            fn = self._get_pipelined_fn()
            cache, _, _ = fn(self.params, jnp.asarray(toks_b)[None],
                             jnp.asarray(miss_b), jnp.asarray(io_pos_b),
                             cache, jnp.int32(n_total))
            # the io_callbacks fire while the dispatch executes; join it
            # before unpublishing the feed
            jax.block_until_ready(cache[self.codec.leaves[0]])
        finally:
            _ACTIVE_FEED = None
        return cache

    def _get_pipelined_fn(self):
        ck = (self._fp, self.cfg.window, self.cfg.n_sinks, "pipelined")
        fn = _jit_cache_get(ck)
        if fn is None:
            fn = jax.jit(
                functools.partial(self.model.recompute_pipelined,
                                  fetch=_feed_fetch,
                                  window=self.cfg.window,
                                  n_sinks=self.cfg.n_sinks))
            _jit_cache_put(ck, fn)
        return fn
