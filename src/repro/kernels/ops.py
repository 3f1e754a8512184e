"""Public kernel entry points.

Each op dispatches to the Pallas TPU kernel on TPU backends and to the
pure-jnp oracle (ref.py) elsewhere.  ``force`` overrides for testing:
  "pallas"     - pallas_call compiled for the current backend
  "interpret"  - pallas_call in interpret mode (runs anywhere; used by
                 the kernel-vs-oracle test sweeps)
  "ref"        - the jnp oracle
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(force: Optional[str]) -> str:
    if force is not None:
        return force
    return "pallas" if _on_tpu() else "ref"


# --------------------------------------------------------------------- #
# chunk quantization codec
# --------------------------------------------------------------------- #
def chunk_quantize(x: Array, bits: int, force: Optional[str] = None
                   ) -> Tuple[Array, Array]:
    """(T, F) float -> (packed int8 (T*bits//8, F), scales fp32 (F,))."""
    mode = _mode(force)
    if mode == "ref":
        return ref.quantize_ref(x, bits)
    from repro.kernels import chunk_quant
    return chunk_quant.quantize(x, bits, interpret=(mode == "interpret"))


def chunk_dequantize(packed: Array, scale: Array, bits: int, n_tokens: int,
                     dtype=jnp.bfloat16, force: Optional[str] = None) -> Array:
    mode = _mode(force)
    if mode == "ref":
        return ref.dequantize_ref(packed, scale, bits, n_tokens, dtype)
    from repro.kernels import chunk_quant
    return chunk_quant.dequantize(packed, scale, bits, n_tokens, dtype,
                                  interpret=(mode == "interpret"))


# --------------------------------------------------------------------- #
# flash attention with fused Eq.-1 density statistic
# --------------------------------------------------------------------- #
def attn_density(q: Array, k: Array, v: Array, window: int = 0,
                 n_sinks: int = 0, force: Optional[str] = None
                 ) -> Tuple[Array, Array]:
    mode = _mode(force)
    if mode == "ref":
        return ref.attn_density_ref(q, k, v, window, n_sinks)
    from repro.kernels import attn_density as kad
    return kad.attn_density(q, k, v, window, n_sinks,
                            interpret=(mode == "interpret"))


# --------------------------------------------------------------------- #
# decode attention over an int8-quantized KV cache (fused dequant)
# --------------------------------------------------------------------- #
def decode_qattn(q: Array, k_q: Array, v_q: Array, k_scale: Array,
                 v_scale: Array, n_valid, window: int = 0, n_sinks: int = 0,
                 force: Optional[str] = None) -> Array:
    mode = _mode(force)
    if mode == "ref":
        return ref.decode_qattn_ref(q, k_q, v_q, k_scale, v_scale, n_valid,
                                    window, n_sinks)
    from repro.kernels import decode_qattn as kdq
    return kdq.decode_qattn(q, k_q, v_q, k_scale, v_scale, n_valid, window,
                            n_sinks, interpret=(mode == "interpret"))


# --------------------------------------------------------------------- #
# mixed-precision decode attention (bf16 window + int8 quant-resident
# segments, fused dequant behind a per-position select)
# --------------------------------------------------------------------- #
def decode_mqattn(q: Array, k: Array, v: Array, k_q: Array, v_q: Array,
                  k_scale: Array, v_scale: Array, quant_mask: Array,
                  n_valid, window: int = 0, n_sinks: int = 0,
                  force: Optional[str] = None) -> Array:
    mode = _mode(force)
    if mode == "ref":
        return ref.decode_mqattn_ref(q, k, v, k_q, v_q, k_scale, v_scale,
                                     quant_mask, n_valid, window, n_sinks)
    from repro.kernels import decode_qattn as kdq
    return kdq.decode_mqattn(q, k, v, k_q, v_q, k_scale, v_scale,
                             quant_mask, n_valid, window, n_sinks,
                             interpret=(mode == "interpret"))


# --------------------------------------------------------------------- #
# paged decode attention over the bf16 page arena, pages read in place
# --------------------------------------------------------------------- #
def paged_decode_attn(q: Array, k_arena: Array, v_arena: Array, layer,
                      pt: Array, pos: Array, window: int = 0,
                      n_sinks: int = 0):
    """No jnp oracle: the gather path (``models/common.py``
    ``paged_cache_view``) is the CPU implementation.  The kernel is
    chosen by the platform the program is lowered for: compiled on a
    TPU, interpreted elsewhere."""
    from repro.kernels import paged_attn as kpa
    call = functools.partial(kpa.paged_decode_attn, q, k_arena, v_arena,
                             layer, pt, pos, window, n_sinks)
    return jax.lax.platform_dependent(
        tpu=lambda: call(interpret=False),
        default=lambda: call(interpret=True))
