"""Output tokens that landed inside the window, over the window's
length (host clock)."""


def read(obs):
    lo, hi = obs["t0"], obs["t_end"]
    n = sum(1 for s in obs["calls"] for t in s.token_times if lo <= t < hi)
    return n / (hi - lo)
