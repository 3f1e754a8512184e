"""Pallas TPU kernel: chunk-wise KV quantization codec (paper §3.2/§4).

The paper packs sub-byte codes "with parallel bit-shift operations" on a
phone CPU; the TPU-native version tiles (chunk-tokens x channels) blocks
into VMEM, computes per-channel symmetric scales on the VPU, and packs
2/4-bit codes into int8 lanes with shifts.  Channel tiles are 128-lane
aligned; the token axis (16 by default) sits on sublanes.

Packed byte ``r`` holds tokens ``r*per .. r*per + per-1`` (``per`` codes
per byte).  The wrappers move the ``per`` tokens that share a byte onto a
leading axis — ``(T, F) -> (per, T/per, F)`` — so the kernels pack and
unpack with whole-tile loads and shifts, never strided sublane slices.
Scales travel as ``(1, F)`` rows so their blocks tile like the data.

Matches kernels/ref.py bit-exactly (tests sweep shapes/dtypes).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import qmax_for

Array = jax.Array
LANES = 128


def _quant_kernel(x_ref, packed_ref, scale_ref, *, bits: int):
    per = x_ref.shape[0]
    qm = qmax_for(bits)
    xs = [x_ref[j].astype(jnp.float32) for j in range(per)]    # (R, BF)
    amax = jnp.max(jnp.abs(xs[0]), axis=0, keepdims=True)
    for x in xs[1:]:
        amax = jnp.maximum(amax, jnp.max(jnp.abs(x), axis=0, keepdims=True))
    s = jnp.maximum(amax / qm, 1e-8)                           # (1, BF)
    scale_ref[...] = s
    mask = (1 << bits) - 1
    acc = None
    for j, x in enumerate(xs):
        c = jnp.clip(jnp.round(x / s), -qm, qm).astype(jnp.int32)
        if bits != 8:
            c = (c & mask) << (bits * j)                       # two's complement
        acc = c if acc is None else acc | c
    # the byte as a signed value, so the narrowing cast never wraps
    acc = jnp.where(acc >= 128, acc - 256, acc)
    packed_ref[...] = acc.astype(jnp.int8)


def _dequant_kernel(packed_ref, scale_ref, o_ref, *, bits: int):
    per = o_ref.shape[0]
    s = scale_ref[...]                                         # (1, BF)
    p = packed_ref[...].astype(jnp.int32)                      # (R, BF)
    mask = (1 << bits) - 1
    half = 1 << (bits - 1)
    u = p & 0xFF                                               # as unsigned byte
    for j in range(per):
        if bits == 8:
            c = p
        else:
            c = (u >> (bits * j)) & mask
            c = jnp.where(c >= half, c - (1 << bits), c)
        o_ref[j] = (c.astype(jnp.float32) * s).astype(o_ref.dtype)


def _pad_to(x: Array, mult: int, axis: int) -> Tuple[Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _block_width(Fp: int) -> int:
    bf = min(Fp, 512)
    while Fp % bf:
        bf //= 2
    return bf


def quantize(x: Array, bits: int, interpret: bool = False
             ) -> Tuple[Array, Array]:
    """x: (T, F) -> (packed (T*bits//8, F) int8, scales (F,) fp32)."""
    assert bits in (8, 4, 2)
    T, F = x.shape
    per = 8 // bits
    assert T % per == 0, (T, bits)
    R = T // per
    xp, pad = _pad_to(x, LANES, 1)
    Fp = xp.shape[1]
    bf = _block_width(Fp)
    xs = xp.reshape(R, per, Fp).transpose(1, 0, 2)             # (per, R, Fp)
    packed, scale = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=(Fp // bf,),
        in_specs=[pl.BlockSpec((per, R, bf), lambda i: (0, 0, i))],
        out_specs=[pl.BlockSpec((R, bf), lambda i: (0, i)),
                   pl.BlockSpec((1, bf), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((R, Fp), jnp.int8),
                   jax.ShapeDtypeStruct((1, Fp), jnp.float32)],
        interpret=interpret,
    )(xs)
    scale = scale[0]
    if pad:
        packed, scale = packed[:, :F], scale[:F]
    return packed, scale


def dequantize(packed: Array, scale: Array, bits: int, n_tokens: int,
               dtype=jnp.bfloat16, interpret: bool = False) -> Array:
    assert bits in (8, 4, 2)
    per = 8 // bits
    R, F = packed.shape
    assert R * per == n_tokens, (R, bits, n_tokens)
    pp, pad = _pad_to(packed, LANES, 1)
    sp, _ = _pad_to(scale.reshape(1, F), LANES, 1)
    Fp = pp.shape[1]
    bf = _block_width(Fp)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits),
        grid=(Fp // bf,),
        in_specs=[pl.BlockSpec((R, bf), lambda i: (0, i)),
                  pl.BlockSpec((1, bf), lambda i: (0, i))],
        out_specs=pl.BlockSpec((per, R, bf), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((per, R, Fp), dtype),
        interpret=interpret,
    )(pp, sp)
    out = out.transpose(1, 0, 2).reshape(n_tokens, Fp)         # token r*per+j
    return out[:, :F] if pad else out
