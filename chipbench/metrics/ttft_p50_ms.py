"""Median time from a call's due time to its first token, over every
call due in the window (host clock)."""
from chipbench.readlib import pct, ttfts_ms


def read(obs):
    return pct(ttfts_ms(obs), 50)
