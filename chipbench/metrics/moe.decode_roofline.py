"""Roofline share of a routed model's decode round: the least time its
must-read HBM bytes take at the chip's bandwidth (``moe_bytes``: the
weights outside the routed experts, the experts hit, the latent pages
attended, each per round from the program's ``moe.experts_hit``,
``moe.decode_rounds`` and ``decode.pages_attended`` counters), over the
device time of the paged decode program per traced round
(``jit__unknown`` under ``service.decode_round``).  A program without
those counters gives nothing."""
from chipbench import flops, moe_bytes
from chipbench.readlib import programs
from chipbench.trace_reduce import time_of


def read(obs):
    progs = programs(obs)
    tel = obs["router"].get("telemetry")
    if progs is None or not tel:
        return None
    c = tel["counters"]
    rounds = c.get("moe.decode_rounds", 0)
    t, n = time_of(progs, r"^jit__unknown$", "service.decode_round")
    if not rounds or not n:
        return None
    cs = int(obs["mix"]["service"].get("chunk_tokens", 16))
    need = moe_bytes.decode_round_bytes(
        obs["cfg"], cs, c.get("moe.experts_hit", 0) / rounds,
        c.get("decode.pages_attended", 0) / rounds)
    bw = flops.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (t / n)
