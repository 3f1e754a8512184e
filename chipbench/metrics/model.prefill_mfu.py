"""Model FLOPs of the paged extend (prefill) programs over their device
time, as a share of the chip's bf16 peak.  The FLOPs are counted from
the prompt length and offset of every extend the service ran while the
trace was on (``flops.dense_extend_flops``); the device time is that of
the unnamed jitted program the service enqueues inside ``begin_call``."""
from chipbench import flops
from chipbench.readlib import in_trace, programs
from chipbench.trace_reduce import time_of


def read(obs):
    progs = programs(obs)
    if progs is None or obs["probes"] is None:
        return None
    t, n = time_of(progs, r"^jit__unknown$", "service.begin_call")
    work = sum(flops.dense_extend_flops(obs["cfg"], m, n0)
               for ts, m, n0 in obs["probes"].extends if in_trace(obs, ts))
    if not n or not work:
        return None
    peak = flops.peaks(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (t * peak)
