"""Roofline share of the chunk codec (``kernels/chunk_quant.py``): the
least time the chip could take for every codec call traced (the larger
of bytes over HBM bandwidth and operations over peak), over the device
time of the codec programs."""
from chipbench import flops
from chipbench.readlib import in_trace, programs
from chipbench.trace_reduce import time_of


def read(obs):
    progs = programs(obs)
    if progs is None or obs["probes"] is None:
        return None
    t, n = time_of(progs, r"^jit_chunk_(de)?quantize$")
    if not n:
        return None
    pk = flops.peaks(obs["device_kind"])
    least = sum(max(flops.chunk_codec_bytes(shp, b, q) / pk["hbm_bytes_per_s"],
                    flops.chunk_codec_flops(shp, b, q) / pk["bf16_flops_per_s"])
                for ts, shp, b, q in obs["probes"].codec if in_trace(obs, ts))
    if not least:
        return None
    return 100.0 * least / t
