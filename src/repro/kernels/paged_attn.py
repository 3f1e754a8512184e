"""Pallas TPU kernel: one decode round's attention over the paged KV
pool, reading each row's K/V pages where they lie in the bf16 arena.

The arena stays in HBM (``memory_space=ANY``); the page table, the
positions and the layer index arrive as scalar-prefetch operands, and
the kernel DMAs ``ppb`` pages at a time, double-buffered, into VMEM.
Row b reads only the blocks that hold its positions ``< pos[b]``, so its
cost follows the live context and not the page-table width; a pad row
(page row all 0, ``pos`` 0) reads nothing.

A page ``(cs, KV, hd)`` is taken as one ``(cs*KV, hd)`` block whose row
``t*KV + n`` is token t of kv head n.  Every query head scores every
row of a block in one matmul, and a row belongs to a head only where
``n == h // G``: the other scores are masked out.  That spends KV times
the minimum of MXU work, which is nothing next to the bytes, and needs
no relayout of the page.  A 0/1 matmul then folds each head's own
scores into a ``(H, ppb*cs)`` tile for the raw-score output.

Outputs, per row and query head, over the cached positions ``< pos``
(causal, with ``window``/``n_sinks`` where nonzero, as in
``models/common.py::decode_attention`` with the query at ``pos``):
the unnormalised f32 accumulator ``sum_t exp(s_t - m) v_t``, the
running max ``m`` and sum ``l``, and the raw scaled scores ``s`` (B, H,
S) in f32, which are undefined where masked.  The round's own token
joins outside (``models/common.py::paged_decode_attention``).

Oracle: the gather path, ``paged_cache_view`` + ``decode_attention``
(tests/test_paged_decode_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def pages_per_block(cs: int) -> int:
    """Pages a DMA block holds: a block spans 128 positions where the
    page size divides 128, so the raw-score tile is whole lanes."""
    return max(1, 128 // cs)


def _fold(x, fold):
    """x (H, R) f32 @ fold (R, T) 0/1 bf16, exactly: each output sums
    one nonzero term, so three bf16 passes over x's 24 mantissa bits
    give it back unrounded."""
    out = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        d = jnp.dot(part, fold, preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def _kernel(layer_ref, pt_ref, pos_ref, q_ref, tok_ref, head_ref, fold_ref,
            k_hbm, v_hbm, acc_ref, m_ref, l_ref, s_ref, kbuf, vbuf, sem, *,
            ppb, cs, kv, n_pages, scale, window, n_sinks):
    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    T = ppb * cs                                   # positions a block
    rows = cs * kv                                 # arena rows a page
    n_blk = (pos + T - 1) // T

    def copies(blk, slot):
        out = []
        for j in range(ppb):
            c = jnp.minimum(blk * ppb + j, n_pages - 1)
            page = pt_ref[b * n_pages + c]
            dst = pl.ds(j * rows, rows)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, dst], sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, dst], sem.at[1, slot]))
        return out

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(n_blk > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()

    q = q_ref[0]                                   # (H, hd)
    tok = tok_ref[...]                             # (1, T*kv): token of a row
    head_ok = head_ref[...] != 0                   # (H, T*kv)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _next():
            for cp in copies(blk + 1, 1 - slot):
                cp.start()

        for cp in copies(blk, slot):
            cp.wait()
        k = kbuf[slot]                             # (T*kv, hd)
        v = vbuf[slot]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = blk * T + tok
        valid = head_ok & (k_pos < pos)
        if window > 0:
            valid = valid & ((k_pos > pos - window) | (k_pos < n_sinks))
        sm = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[0][:, :1]                   # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sm, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(sm - m_new), 0.0)
        l_new = l_ref[0][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[0] = acc_ref[0] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[0] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        s_ref[0, :, pl.ds(pl.multiple_of(blk * T, T), T)] = _fold(
            jnp.where(head_ok, s, 0.0), fold_ref[...])
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)


def paged_decode_attn(q: Array, k_arena: Array, v_arena: Array, layer,
                      pt: Array, pos: Array, window: int = 0,
                      n_sinks: int = 0, interpret: bool = False):
    """q (B, H, hd) bf16; arenas (L, P, cs, KV, hd) bf16; layer () int;
    pt (B, C) int32 page rows; pos (B,) int32 cached positions a row.
    -> (acc (B, H, hd), m (B, H), l (B, H), s (B, H, C*cs)), all f32."""
    B, H, hd = q.shape
    L, P, cs, KV, _ = k_arena.shape
    C = pt.shape[1]
    G = H // KV
    ppb = pages_per_block(cs)
    T = ppb * cs
    n_blk = -(-C // ppb)
    S = C * cs
    rows = cs * KV
    # per row of a block: the token it holds, and which heads it serves
    r = np.arange(T * KV)
    tok = jnp.asarray((r // KV)[None], jnp.int32)
    head_ok = jnp.asarray(
        (r[None] % KV == np.arange(H)[:, None] // G), jnp.int32)
    fold = jnp.asarray((r[:, None] // KV) == np.arange(T)[None],
                       jnp.bfloat16)
    ka = k_arena.reshape(L, P, rows, hd)
    va = v_arena.reshape(L, P, rows, hd)

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    def per_row(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b, *_: (b,) + (0,) * len(shape))

    itemsize = k_arena.dtype.itemsize
    cost = pl.CostEstimate(
        flops=4 * B * H * S * KV * hd + 6 * B * H * S * KV * T,
        transcendentals=B * H * S * KV,
        bytes_accessed=(2 * B * C * rows * hd * itemsize
                        + B * H * (S + hd) * 4 + q.size * q.dtype.itemsize))
    acc, m, l, s = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, cs=cs, kv=KV, n_pages=C,
                          scale=1.0 / float(np.sqrt(hd)), window=window,
                          n_sinks=n_sinks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[per_row((H, hd)), whole((1, T * KV)),
                      whole((H, T * KV)), whole((T * KV, T)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[per_row((H, hd)), per_row((H, 128)),
                       per_row((H, 128)), per_row((H, n_blk * T))],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * rows, hd), k_arena.dtype),
                pltpu.VMEM((2, ppb * rows, hd), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, n_blk * T), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      pt.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32), q, tok,
      head_ok, fold, ka, va)
    return acc, m[..., 0], l[..., 0], s[..., :S]
