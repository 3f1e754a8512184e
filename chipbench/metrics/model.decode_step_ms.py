"""Device time of one paged decode program, averaged over the decode
rounds traced: the unnamed jitted program (``jit__unknown``) that the
service enqueues inside a decode round."""
from chipbench.readlib import programs
from chipbench.trace_reduce import time_of


def read(obs):
    progs = programs(obs)
    if progs is None:
        return None
    t, n = time_of(progs, r"^jit__unknown$", "service.decode_round")
    return 1e3 * t / n if n else None
