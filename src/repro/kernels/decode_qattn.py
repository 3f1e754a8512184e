"""Pallas TPU kernel: single-token decode attention over an INT8 KV
cache with FUSED dequantization.

The serving hot path for LLMS: resident chunks live compressed (int8 +
per-(token, kv-head) scales); attention dequantizes inside VMEM instead
of materializing a bf16 cache in HBM, which halves the bytes a decode
step reads from the cache.

Layout: q (B,H,hd); caches (B,S,KV,hd) int8; scales (B,S,KV) fp32.
Grid (B, KV, nS) — S innermost, online softmax in VMEM scratch, G=H/KV
query heads processed together as the matmul M dimension.  The wrappers
hand the kernels a heads-major view — caches (B,KV,S,hd), scales and
the quant mask as (B,KV|1,S,1) columns — so every block is a whole
(bs, hd) or (bs, 1) tile, which is what Mosaic accepts; ``n_valid``
arrives as a scalar-prefetch operand in SMEM.

Oracle: kernels/ref.py::decode_qattn_ref.
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


def _attend_block(q, k, v, nv, o_ref, acc, mx, lx, *, bs, ns, scale, S,
                  window, n_sinks):
    """One online-softmax step of G query heads over a key block; q
    (G, hd), k/v (bs, hd) fp32.  Writes ``o_ref`` after the last block."""
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        mx[...] = jnp.full_like(mx, NEG_INF)
        lx[...] = jnp.zeros_like(lx)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = js * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = (k_pos < nv) & (k_pos < S)
    if window > 0:
        valid = valid & ((k_pos >= nv - window) | (k_pos < n_sinks))
    s = jnp.where(valid, s, NEG_INF)                    # (G, bs)
    m_prev = mx[...]                                    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    lx[...] = lx[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    mx[...] = m_new

    @pl.when(js == ns - 1)
    def _done():
        o_ref[0, 0] = (acc[...] / jnp.maximum(lx[...], 1e-30)
                       ).astype(o_ref.dtype)


def _kernel(nv_ref, q_ref, kq_ref, vq_ref, ks_ref, vs_ref, o_ref,
            acc, mx, lx, **kw):
    q = q_ref[0, 0].astype(jnp.float32)                 # (G, hd)
    k = kq_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]   # (bs, hd)
    v = vq_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    _attend_block(q, k, v, nv_ref[pl.program_id(0)], o_ref, acc, mx, lx,
                  **kw)


def _heads_major(x: Array, Sp: int) -> Array:
    """Pad the sequence axis to Sp and put kv heads first:
    (B, S, KV, hd) -> (B, KV, Sp, hd); (B, S, KV) -> (B, KV, Sp, 1)."""
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, Sp - x.shape[1])
    x = jnp.swapaxes(jnp.pad(x, widths), 1, 2)
    return x if x.ndim == 4 else x[..., None]


def _decode_call(kernel, q, operands, n_valid, S, *, window, n_sinks, bs,
                 interpret):
    """Run a decode kernel over (B, KV|1, Sp, *) ``operands`` blocked
    (1, 1, bs, *) along the sequence; a size-1 head axis is shared by
    every kv head."""
    B, H, hd = q.shape
    KV, Sp = operands[0].shape[1], operands[0].shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (B,))

    def seq_spec(a):
        if a.shape[1] == 1:
            return pl.BlockSpec((1, 1, bs, a.shape[-1]),
                                lambda b, n, j, nv_ref: (b, 0, j, 0))
        return pl.BlockSpec((1, 1, bs, a.shape[-1]),
                            lambda b, n, j, nv_ref: (b, n, j, 0))

    head_spec = pl.BlockSpec((1, 1, G, hd),
                             lambda b, n, j, nv_ref: (b, n, 0, 0))
    ns = Sp // bs
    out = pl.pallas_call(
        functools.partial(kernel, bs=bs, ns=ns,
                          scale=1.0 / float(np.sqrt(hd)), S=S,
                          window=window, n_sinks=n_sinks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, ns),
            in_specs=[head_spec] + [seq_spec(a) for a in operands],
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(nv, qg, *operands)
    return out.reshape(B, H, hd)


def _seq_blocks(S: int, bs: int):
    """-> (block length, padded length): blocks are sublane multiples."""
    bs = min(bs, -(-S // 8) * 8)
    return bs, -(-S // bs) * bs


def decode_qattn(q: Array, k_q: Array, v_q: Array, k_scale: Array,
                 v_scale: Array, n_valid, window: int = 0, n_sinks: int = 0,
                 interpret: bool = False, bs: int = 256) -> Array:
    """q (B,H,hd); k_q/v_q (B,S,KV,hd) int8; scales (B,S,KV) fp32;
    n_valid () or (B,).  Returns (B,H,hd) in q.dtype."""
    S = k_q.shape[1]
    bs, Sp = _seq_blocks(S, bs)
    operands = [_heads_major(a, Sp) for a in (k_q, v_q, k_scale, v_scale)]
    return _decode_call(_kernel, q, operands, n_valid, S, window=window,
                        n_sinks=n_sinks, bs=bs, interpret=interpret)


# --------------------------------------------------------------------- #
# Mixed-precision decode attention: bf16 recent window + int8
# quant-resident chunk segments, selected per position by quant_mask and
# dequantized in VMEM (the quant-resident residency tier's hot path).
# --------------------------------------------------------------------- #
def _mixed_kernel(nv_ref, q_ref, k_ref, v_ref, kq_ref, vq_ref, ks_ref,
                  vs_ref, qm_ref, o_ref, acc, mx, lx, **kw):
    q = q_ref[0, 0].astype(jnp.float32)                 # (G, hd)
    m = qm_ref[0, 0] != 0                               # (bs, 1)
    # fused dequant THROUGH the storage dtype: a quant position must
    # contribute exactly the value a full dequantization would have
    # materialized into the bf16 cache (token-identity contract)
    kd = (kq_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
          ).astype(k_ref.dtype).astype(jnp.float32)
    vd = (vq_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
          ).astype(v_ref.dtype).astype(jnp.float32)
    k = jnp.where(m, kd, k_ref[0, 0].astype(jnp.float32))
    v = jnp.where(m, vd, v_ref[0, 0].astype(jnp.float32))
    _attend_block(q, k, v, nv_ref[pl.program_id(0)], o_ref, acc, mx, lx,
                  **kw)


def decode_mqattn(q: Array, k: Array, v: Array, k_q: Array, v_q: Array,
                  k_scale: Array, v_scale: Array, quant_mask: Array,
                  n_valid, window: int = 0, n_sinks: int = 0,
                  interpret: bool = False, bs: int = 256) -> Array:
    """q (B,H,hd); k/v (B,S,KV,hd) bf16; k_q/v_q (B,S,KV,hd) int8;
    scales (B,S,KV) fp32; quant_mask (B,S) bool; n_valid () or (B,).
    Returns (B,H,hd) in q.dtype.  Oracle: ref.py::decode_mqattn_ref."""
    S = k.shape[1]
    bs, Sp = _seq_blocks(S, bs)
    operands = [_heads_major(a, Sp)
                for a in (k, v, k_q, v_q, k_scale, v_scale)]
    mask = _heads_major(quant_mask.astype(jnp.int32)[:, :, None], Sp)
    return _decode_call(_mixed_kernel, q, operands + [mask], n_valid, S,
                        window=window, n_sinks=n_sinks, bs=bs,
                        interpret=interpret)
