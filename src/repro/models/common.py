"""Shared model primitives: norms, rotary embeddings, attention.

Everything here is written for GSPMD-friendliness:
  * masks and rotary tables are built ON THE FLY from ``broadcasted_iota``
    (never as materialized constants -- a 32k x 32k boolean mask constant
    would explode compile memory);
  * GQA never materializes repeated K/V heads (grouped einsums);
  * long-sequence prefill uses a blocked online-softmax (flash-style) scan
    so the per-layer temp is one (B, H, Sq, block) tile, not (B, H, Sq, Sk).

``key_density`` is the paper's Eq. (1) information-density statistic: the
mean attention mass each key token receives from the queries that can see
it, averaged over heads (the caller accumulates layers and chunks).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


# --------------------------------------------------------------------- #
# Activation sharding constraints.  GSPMD propagation can drop the batch
# sharding across a layer scan (the embed table is (model, data)-sharded,
# so the scan carry's initial sharding is ambiguous and everything
# downstream silently replicates -- x16 activation memory on the 16x16
# mesh).  Launchers opt in via set_batch_axes(("data",)) /
# (("pod","data")); the default (None) is a no-op so single-device tests
# and the CPU service never see a mesh requirement.
# --------------------------------------------------------------------- #
_BATCH_AXES = None


def set_batch_axes(axes):
    global _BATCH_AXES
    _BATCH_AXES = tuple(axes) if axes else None


def constrain_batch(x: Array) -> Array:
    """Pin dim 0 of an activation to the data axes (no-op by default)."""
    if _BATCH_AXES is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(_BATCH_AXES, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------- #
def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def group_norm_heads(x: Array, scale: Array, bias: Array, n_heads: int,
                     eps: float = 1e-5) -> Array:
    """GroupNorm with one group per head over the last dim (RWKV ln_x)."""
    dt = x.dtype
    *lead, d = x.shape
    x = x.astype(jnp.float32).reshape(*lead, n_heads, d // n_heads)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = ((x - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------- #
# Rotary position embeddings (computed on the fly from positions)
# --------------------------------------------------------------------- #
def rope_angles(positions: Array, head_dim: int, theta: float) -> Tuple[Array, Array]:
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2), fp32."""
    half = head_dim // 2
    freqs = jnp.exp(
        jnp.arange(half, dtype=jnp.float32) * (-np.log(theta) / half)
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., n_heads, head_dim); cos/sin broadcastable to (..., 1, hd//2).

    Rotate-half convention (llama): pairs are (x[:d/2], x[d/2:]).
    """
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(dt)


def sinusoidal_positions(n_pos: int, d_model: int) -> Array:
    """Whisper-style fixed sinusoidal embeddings, built from iota."""
    pos = jax.lax.broadcasted_iota(jnp.float32, (n_pos, 1), 0)
    half = d_model // 2
    i = jax.lax.broadcasted_iota(jnp.float32, (1, half), 1)
    inv = jnp.exp(i * (-np.log(10000.0) / max(half - 1, 1)))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------- #
# Masks (built from iota; never materialized as host constants)
# --------------------------------------------------------------------- #
def causal_window_mask(q_pos: Array, k_pos: Array, window: int = 0,
                       n_sinks: int = 0) -> Array:
    """Boolean (..., Sq, Sk) mask. True == attend.

    window > 0 enables the paper's streaming mode: each query sees the
    last `window` tokens plus the first `n_sinks` sink tokens
    (StreamingLLM, paper section 4).
    """
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    m = k <= q
    if window > 0:
        in_window = k > (q - window)
        is_sink = k < n_sinks
        m = m & (in_window | is_sink)
    return m


# --------------------------------------------------------------------- #
# Grouped-query attention (full materialization; small/medium sequences)
# --------------------------------------------------------------------- #
class AttnOut(NamedTuple):
    out: Array                       # (B, Sq, H, hd)
    key_density: Optional[Array]     # (B, Sk) fp32 or None


def gqa_attention(q: Array, k: Array, v: Array, mask: Array,
                  want_density: bool = False,
                  softcap: float = 0.0) -> AttnOut:
    """q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd); mask: bool broadcastable
    (B?,1?,Sq,Sk).  Never repeats KV heads."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    maskb = mask[None] if mask.ndim == 2 else mask           # (B|1, Sq, Sk)
    s = jnp.where(maskb[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v)
    out = out.reshape(B, Sq, H, v.shape[-1])
    density = None
    if want_density:
        # Eq. (1): per key, mean attention received over valid (row) queries
        mass = jnp.sum(p, axis=(1, 2, 3))                         # (B, Sk)
        nvalid = jnp.maximum(jnp.sum(maskb, axis=1), 1)           # (B|1, Sk)
        density = (mass / (H * nvalid)).astype(jnp.float32)
    return AttnOut(out, density)


# --------------------------------------------------------------------- #
# Blocked (flash-style) causal attention via lax.scan over key blocks.
# Temp footprint: one (B, KV, G, Sq, block) tile instead of (..., Sq, Sk).
# --------------------------------------------------------------------- #
def blocked_causal_attention(q: Array, k: Array, v: Array,
                             q_offset: int = 0,
                             block: int = 1024,
                             window: int = 0,
                             n_sinks: int = 0,
                             want_density: bool = False) -> AttnOut:
    """Causal GQA over long sequences.  q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd).
    q token i has absolute position q_offset + i; k token j has position j.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    nblk = (Sk + block - 1) // block
    pad = nblk * block - Sk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(B, nblk, block, KV, k.shape[-1]).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nblk, block, KV, v.shape[-1]).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, Sq, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq,), 0)

    vd = v.shape[-1]
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KV, G, vd), jnp.float32)

    def step(carry, blk):
        m, l, acc, idx = carry[0], carry[1], carry[2], carry[3]
        kblk, vblk = blk
        k_pos = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = causal_window_mask(q_pos, k_pos, window, n_sinks)
        valid = valid & (k_pos < Sk)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bngqk,bknd->bqngd", p.astype(vblk.dtype), vblk)
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new, idx + 1), None

    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, acc0, jnp.int32(0)),
                                     (kb, vb))
    l_t = l.transpose(0, 3, 1, 2)[..., None]
    out = (acc / jnp.maximum(l_t, 1e-30)).astype(q.dtype).reshape(B, Sq, H, vd)

    density = None
    if want_density:
        # second pass: accumulate normalized attention mass per key
        def dstep(idx, _):
            kblk = kb[idx]
            k_pos = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
            s = jnp.einsum("bqngd,bknd->bngqk", qg, kblk,
                           preferred_element_type=jnp.float32) * scale
            valid = causal_window_mask(q_pos, k_pos, window, n_sinks)
            valid = valid & (k_pos < Sk)[None, :]
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            p = jnp.exp(s - m[..., None]) / jnp.maximum(l[..., None], 1e-30)
            mass = jnp.sum(p, axis=(1, 2, 3))                      # (B, blk)
            nvalid = jnp.maximum(jnp.sum(valid, axis=0), 1)        # (blk,)
            return (mass / (H * nvalid[None, :])).astype(jnp.float32)

        idxs = jnp.arange(nblk)
        masses = jax.lax.map(lambda i: dstep(i, None), idxs)        # (nblk,B,blk)
        density = masses.transpose(1, 0, 2).reshape(B, nblk * block)[:, :Sk]
    return AttnOut(out, density)


# --------------------------------------------------------------------- #
# Flash attention with a custom VJP (training path).
#
# Differentiating through the blocked-attention scan makes XLA save the
# per-step softmax carries for backward — ~4 GiB * n_blocks per layer at
# 4k context, the dominant train-memory term (EXPERIMENTS.md §Perf).
# The custom backward recomputes scores block-by-block from the saved
# (q, k, v, out, m, l): standard flash backward, O(block) temporaries.
# --------------------------------------------------------------------- #
def _flash_blocks(k, v, block):
    B, Sk, KV = k.shape[:3]
    nblk = (Sk + block - 1) // block
    pad = nblk * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block, KV, k.shape[-1]).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block, KV, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    return kb, vb, nblk


def _flash_fwd_impl(q, k, v, q_offset, block, window, n_sinks):
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    kb, vb, nblk = _flash_blocks(k, v, block)
    qg = q.reshape(B, Sq, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq,), 0)

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KV, G, v.shape[-1]), jnp.float32)

    def step(carry, blk):
        m, l, acc, idx = carry
        kblk, vblk = blk
        k_pos = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = causal_window_mask(q_pos, k_pos, window, n_sinks)
        valid = valid & (k_pos < Sk)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bngqk,bknd->bqngd", p.astype(vblk.dtype), vblk)
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new, idx + 1), None

    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, acc0, jnp.int32(0)),
                                     (kb, vb))
    l_t = l.transpose(0, 3, 1, 2)[..., None]
    out = (acc / jnp.maximum(l_t, 1e-30)).astype(q.dtype)  # (B,Sq,KV,G,vd)
    return out.reshape(B, Sq, H, v.shape[-1]), m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, q_offset=0, block=1024, window=0, n_sinks=0):
    out, _, _ = _flash_fwd_impl(q, k, v, q_offset, block, window, n_sinks)
    return out


def _flash_vjp_fwd(q, k, v, q_offset, block, window, n_sinks):
    out, m, l = _flash_fwd_impl(q, k, v, q_offset, block, window, n_sinks)
    return out, (q, k, v, out, m, l)


def _flash_vjp_bwd(q_offset, block, window, n_sinks, res, dout):
    q, k, v, out, m, l = res
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    vd = v.shape[-1]
    kb, vb, nblk = _flash_blocks(k, v, block)
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    do = dout.reshape(B, Sq, KV, G, vd).astype(jnp.float32)
    og = out.reshape(B, Sq, KV, G, vd).astype(jnp.float32)
    scale = 1.0 / np.sqrt(hd)
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq,), 0)
    # delta = rowsum(dout * out): (B,KV,G,Sq)
    delta = jnp.sum(do * og, axis=-1).transpose(0, 2, 3, 1)
    lsafe = jnp.maximum(l, 1e-30)

    def step(dq, blk):
        kblk, vblk, idx = blk
        k_pos = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kblk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        valid = causal_window_mask(q_pos, k_pos, window, n_sinks)
        valid = valid & (k_pos < Sk)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / lsafe[..., None]      # (B,n,g,q,k)
        dv = jnp.einsum("bngqk,bqngd->bknd", p, do)
        dp = jnp.einsum("bqngd,bknd->bngqk", do,
                        vblk.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bngqk,bknd->bqngd", ds,
                             kblk.astype(jnp.float32))
        dk = jnp.einsum("bngqk,bqngd->bknd", ds, qg)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    idxs = jnp.arange(nblk, dtype=jnp.int32)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, (kb, vb, idxs))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, nblk * block, KV, hd)[:, :Sk]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, nblk * block, KV, vd)[:, :Sk]
    return (dq.reshape(B, Sq, H, hd).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# --------------------------------------------------------------------- #
# Decode attention against a (possibly quantized) KV cache
# --------------------------------------------------------------------- #
def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cur_pos: Array,
                     k_scale: Optional[Array] = None,
                     v_scale: Optional[Array] = None,
                     window: int = 0, n_sinks: int = 0,
                     want_density: bool = False):
    """One-step attention.  q: (B,1,H,hd); caches: (B,S,KV,hd) in bf16 or
    int8 (with per (B,S,KV) scales).  cur_pos: () or (B,) -- number of
    valid cache entries; the new token attends to cache[:cur_pos].
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if k_scale is not None:
        k = (k_cache.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
        v = (v_cache.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    else:
        k, v = k_cache, v_cache
    qg = q.reshape(B, 1, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
    pos = jnp.asarray(cur_pos)
    pos_b = pos if pos.ndim else pos[None].repeat(B, 0)
    valid = k_pos[None, :] < pos_b[:, None]                    # (B, S)
    if window > 0:
        in_win = k_pos[None, :] >= (pos_b[:, None] - window)
        sink = k_pos[None, :] < n_sinks
        valid = valid & (in_win | sink)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v)
    out = out.reshape(B, 1, H, v.shape[-1])
    if want_density:
        mass = (jnp.sum(p, axis=(1, 2, 3)) / H).astype(jnp.float32)  # (B, S)
        return out, mass
    return out


# --------------------------------------------------------------------- #
# Mixed-precision decode attention: bf16 recent window + int8
# quant-resident chunk segments (fused dequant, selected per position).
#
# The dequantized value of a quant position is computed THROUGH the
# cache dtype — ``(code * scale) -> bf16`` — i.e. exactly the value a
# full dequantization would have materialized into the bf16 cache, so
# quant-resident decode is bit-identical to the full-dequant path
# (tests/test_quant_resident.py asserts token identity).
# --------------------------------------------------------------------- #

# above this many cache positions the CPU path switches from the
# plain select (bitwise-identical to ``decode_attention``) to the
# blocked online-softmax scan, which dequantizes one key block at a
# time and never materializes the full dequantized cache
MIXED_BLOCKED_MIN_S = 4096


def dequant_select(x_cache: Array, x_q: Array, x_scale: Array,
                   quant_mask: Array) -> Array:
    """Per-position select between the bf16 cache and the fused-dequant
    int8 segments.  x_cache (B,S,KV,hd); x_q int8; x_scale (B,S,KV);
    quant_mask (B,S) bool."""
    dq = (x_q.astype(jnp.float32) * x_scale[..., None]).astype(x_cache.dtype)
    return jnp.where(quant_mask[:, :, None, None], dq, x_cache)


def mixed_decode_attention_blocked(q: Array, k_cache: Array, v_cache: Array,
                                   k_q: Array, v_q: Array, k_scale: Array,
                                   v_scale: Array, quant_mask: Array,
                                   cur_pos: Array, window: int = 0,
                                   n_sinks: int = 0,
                                   want_density: bool = False,
                                   block: int = 1024):
    """Blocked-jnp fused-dequant reference: online softmax over key
    blocks, dequantizing one (B, block, KV, hd) tile at a time — the
    memory-bounded long-context form of ``mixed_decode_attention`` and
    the CPU mirror of the Pallas kernel (kernels/decode_qattn.py::
    decode_mqattn; oracle kernels/ref.py::decode_mqattn_ref)."""
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    nblk = (S + block - 1) // block
    pad = nblk * block - S
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, padw)
        v_cache = jnp.pad(v_cache, padw)
        k_q = jnp.pad(k_q, padw)
        v_q = jnp.pad(v_q, padw)
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
        quant_mask = jnp.pad(quant_mask, ((0, 0), (0, pad)))

    def blks(a):
        r = a.reshape((B, nblk, block) + a.shape[2:])
        return r.transpose((1, 0, 2) + tuple(range(3, r.ndim)))

    kb, vb = blks(k_cache), blks(v_cache)
    kqb, vqb = blks(k_q), blks(v_q)
    ksb, vsb = blks(k_scale), blks(v_scale)
    qmb = quant_mask.reshape(B, nblk, block).transpose(1, 0, 2)

    qg = q.reshape(B, 1, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    pos = jnp.asarray(cur_pos)
    pos_b = pos if pos.ndim else pos[None].repeat(B, 0)    # (B,)

    m0 = jnp.full((B, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, hd), jnp.float32)

    def step(carry, blk):
        m, l, acc, idx = carry
        kc, vc, kq, vq, ks, vs, qm = blk
        kf = dequant_select(kc, kq, ks, qm).astype(jnp.float32)
        vf = dequant_select(vc, vq, vs, qm).astype(jnp.float32)
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kf,
                       preferred_element_type=jnp.float32)[:, :, :, 0] * scale
        k_pos = idx * block + jax.lax.broadcasted_iota(
            jnp.int32, (block,), 0)
        valid = (k_pos[None, :] < pos_b[:, None]) & (k_pos < S)[None, :]
        if window > 0:
            in_win = k_pos[None, :] >= (pos_b[:, None] - window)
            sink = k_pos[None, :] < n_sinks
            valid = valid & (in_win | sink)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bngk,bknd->bngd", p, vf)
        return (m_new, l_new, acc_new, idx + 1), None

    (m, l, acc, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, jnp.int32(0)), (kb, vb, kqb, vqb, ksb, vsb, qmb))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    out = out.reshape(B, 1, H, hd)
    if want_density:
        # second pass over blocks: normalized attention mass per key
        def dstep(idx):
            kf = dequant_select(kb[idx], kqb[idx], ksb[idx],
                                qmb[idx]).astype(jnp.float32)
            s = jnp.einsum("bqngd,bknd->bngqk", qg, kf,
                           preferred_element_type=jnp.float32
                           )[:, :, :, 0] * scale
            k_pos = idx * block + jax.lax.broadcasted_iota(
                jnp.int32, (block,), 0)
            valid = (k_pos[None, :] < pos_b[:, None]) & (k_pos < S)[None, :]
            if window > 0:
                in_win = k_pos[None, :] >= (pos_b[:, None] - window)
                sink = k_pos[None, :] < n_sinks
                valid = valid & (in_win | sink)
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
            p = jnp.exp(s - m[..., None]) / jnp.maximum(l, 1e-30)[..., None]
            return (jnp.sum(p, axis=(1, 2)) / H).astype(jnp.float32)

        masses = jax.lax.map(dstep, jnp.arange(nblk))       # (nblk, B, blk)
        mass = masses.transpose(1, 0, 2).reshape(B, nblk * block)[:, :S]
        return out, mass
    return out


def mixed_decode_attention(q: Array, k_cache: Array, v_cache: Array,
                           k_q: Array, v_q: Array, k_scale: Array,
                           v_scale: Array, quant_mask: Array, cur_pos: Array,
                           window: int = 0, n_sinks: int = 0,
                           want_density: bool = False):
    """One-step attention over a mixed cache.  q: (B,1,H,hd); k/v bf16 and
    k_q/v_q int8 caches (B,S,KV,hd); scales (B,S,KV); quant_mask (B,S).

    Dispatch: Pallas fused kernel on TPU (density falls back to the
    blocked path), blocked online-softmax scan for long caches, plain
    select + ``decode_attention`` numerics otherwise (bit-identical to
    the full-dequant bf16 path)."""
    S = k_cache.shape[1]
    if jax.default_backend() == "tpu" and not want_density:
        from repro.kernels import ops as kops
        pos = jnp.asarray(cur_pos)
        out = kops.decode_mqattn(q[:, 0], k_cache, v_cache, k_q, v_q,
                                 k_scale, v_scale, quant_mask, pos,
                                 window, n_sinks)
        return out[:, None]
    if S >= MIXED_BLOCKED_MIN_S:
        return mixed_decode_attention_blocked(
            q, k_cache, v_cache, k_q, v_q, k_scale, v_scale, quant_mask,
            cur_pos, window, n_sinks, want_density)
    k = dequant_select(k_cache, k_q, k_scale, quant_mask)
    v = dequant_select(v_cache, v_q, v_scale, quant_mask)
    return decode_attention(q, k, v, cur_pos, window=window,
                            n_sinks=n_sinks, want_density=want_density)


# --------------------------------------------------------------------- #
# Paged KV pool: dense cache view over page arenas
# --------------------------------------------------------------------- #
def paged_cache_view(arenas, leaves, pt16, pt8=None, quant_chunks=None,
                     pos=None):
    """Materialize the dense slot-cache view of a paged KV pool.

    ``arenas`` holds per-leaf page arenas: ``<leaf>16`` (L, P16, cs,
    ...) bf16 and — in quant-resident mode — ``<leaf>8`` int8 codes
    plus ``<leaf>8s`` per-(token, kv-head) fp32 scales (L, P8, cs,
    ...).  ``pt16``/``pt8`` are (B, C) page-table rows (one chunk per
    entry, page 0 = scratch); ``quant_chunks`` (B, C) bool marks which
    chunks live in the int8 arena.  The gather produces exactly the
    (L, B, S, ...) mixed-cache layout ``decode_step``/``recompute``
    consume, so every downstream attention op — and therefore every
    emitted token — is bit-identical to the slot-cache path.
    """
    from repro.kernels.paged import gather_pages
    cache = {"pos": pos}
    for n in leaves:
        cache[n] = gather_pages(arenas[n + "16"], pt16)
    if pt8 is not None:
        for n in leaves:
            cache[n + "_q"] = gather_pages(arenas[n + "8"], pt8)
            cache[n + "_scale"] = gather_pages(arenas[n + "8s"], pt8)
        B, C = quant_chunks.shape
        cs = arenas[leaves[0] + "16"].shape[2]
        qm = jnp.broadcast_to(quant_chunks[:, :, None], (B, C, cs))
        # dummy leading axis: axis 1 stays the batch axis for every leaf
        cache["quant_mask"] = qm.reshape(B, C * cs)[None]
    return cache


def on_tpu() -> bool:
    """The backend check of ``paged_decode_in_place`` (tests steer it)."""
    return jax.default_backend() == "tpu"


def paged_decode_in_place(arenas, pt8) -> bool:
    """Whether a paged decode round reads its K/V pages in place
    (``paged_decode_attention``) instead of through
    ``paged_cache_view``.  The dense view is the round's largest cost on
    the chip: the view and its relayouts are written several times over
    for every token.  A Pallas operand must keep XLA's default layout,
    which the compiler gives a bf16 arena only where a page's
    ``(KV, hd)`` rows are whole tiles (``hd`` a multiple of 128); at
    ``hd`` 64 it moves the page axis minor, and a kernel there would pay
    a whole-arena relayout a call.  Quant-resident pages (``pt8``) and
    arenas of other leaves than ``k``/``v`` (MLA's latent ``ckv``/
    ``kpe``) have no kernel here."""
    return (on_tpu() and pt8 is None and "k16" in arenas
            and arenas["k16"].shape[-1] % 128 == 0)


def paged_decode_attention(q: Array, k_new: Array, v_new: Array,
                           k_arena: Array, v_arena: Array, layer, pt16,
                           pos, window: int = 0, n_sinks: int = 0,
                           want_density: bool = False):
    """``decode_attention`` for one layer of a paged round without a
    dense view.  q (B,1,H,hd); k_new/v_new (B,1,KV,hd) the round's own
    token at ``pos`` (B,); arenas (L, P, cs, KV, hd) before the round.
    The kernel attends the cached positions ``< pos`` in place; the new
    token joins its softmax here (the flash-decoding combine).
    -> out (B,1,H,hd)[, mass (B, C*cs) f32: per-key probability summed
    over heads / H, the new token's at ``pos``]."""
    from repro.kernels import ops as kops
    B, _, H, hd = q.shape
    KV = k_new.shape[2]
    G = H // KV
    k_new = k_new.astype(k_arena.dtype)
    v_new = v_new.astype(v_arena.dtype)
    acc, m, l, s = kops.paged_decode_attn(
        q[:, 0], k_arena, v_arena, layer, pt16, pos, window, n_sinks)
    scale = 1.0 / np.sqrt(hd)
    qg = q[:, 0].reshape(B, KV, G, hd)
    s_new = jnp.einsum("bngd,bnd->bng", qg, k_new[:, 0],
                       preferred_element_type=jnp.float32
                       ).reshape(B, H) * scale
    m_f = jnp.maximum(m, s_new)
    a = jnp.exp(m - m_f)
    e_new = jnp.exp(s_new - m_f)
    l_f = l * a + e_new
    v_h = jnp.repeat(v_new[:, 0].astype(jnp.float32), G, axis=1)
    out = (acc * a[..., None] + e_new[..., None] * v_h) / l_f[..., None]
    out = out.astype(q.dtype)[:, None]
    if not want_density:
        return out
    S = s.shape[-1]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)[None, :]
    p_b = pos[:, None]
    valid = k_pos < p_b
    if window > 0:
        valid = valid & ((k_pos > p_b - window) | (k_pos < n_sinks))
    p = jnp.exp(s - m_f[..., None]) / l_f[..., None]
    mass = jnp.sum(jnp.where(valid[:, None], p, 0.0), axis=1)
    mass = mass + jnp.where(k_pos == p_b, jnp.sum(e_new / l_f, axis=1
                                                  )[:, None], 0.0)
    return out, mass / H


# --------------------------------------------------------------------- #
# FFN
# --------------------------------------------------------------------- #
def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x: Array, w_up: Array, b_up: Array, w_down: Array,
             b_down: Array) -> Array:
    h = jax.nn.gelu(x @ w_up + b_up, approximate=True)
    return h @ w_down + b_down


# --------------------------------------------------------------------- #
# Cache update helper
# --------------------------------------------------------------------- #
def ring_update(cache: Array, new: Array, pos: Array, ring: bool = False) -> Array:
    """Write `new` (B,1,...) into cache (B,S,...) at seq index pos.

    pos is a scalar int array (every batch row decodes at the same
    position: the serial working cache) or a (B,) vector of per-row
    positions (multi-context batched decode: row b is an independent
    slot writing at its own offset).  With ring=True the index wraps
    (sliding-window cache)."""
    S = cache.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim:                       # per-slot positions: row-wise write
        idx = pos % S if ring else pos
        # masked select, not .at[] scatter: elementwise select vectorizes
        # ~5x better than gather/scatter machinery on the CPU backend
        s_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
        mask = s_pos[None, :] == idx[:, None]              # (B, S)
        mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
        return jnp.where(mask, new.astype(cache.dtype), cache)
    idx = pos % S if ring else pos
    start = [jnp.asarray(0, jnp.int32)] * cache.ndim
    start[1] = jnp.asarray(idx, jnp.int32)
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                        tuple(start))


def init_linear(key, shape, scale: float = 0.02, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
