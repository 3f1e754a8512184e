"""MiB read back from the swap tier per finished window call (the
service's process-wide IO counters, over the window and its drain)."""


def read(obs):
    n = sum(s.ok for s in obs["calls"])
    return obs["io_read"] / 2**20 / n if n else None
