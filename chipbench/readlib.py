"""Helpers the metric readers share.  A reader that finds nothing to
read returns None, and the harness leaves its metric out."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def ttfts_ms(obs) -> List[float]:
    """Due time to first token, for every window call that produced one."""
    return [(s.token_times[0] - s.due) * 1e3 for s in obs["calls"]
            if s.token_times]


def in_trace(obs, t: float) -> bool:
    return obs["trace_t0"] <= t <= obs["trace_t1"]


def programs(obs):
    tr = obs["trace"]
    return None if tr is None else tr["programs"]


def token_gaps_ms(obs) -> List[float]:
    """Every gap between consecutive tokens of a window call.  Where the
    cell stops at the window's end (no drain), only gaps whose two
    tokens both landed inside the window."""
    lo, hi = obs["t0"], obs["t_end"]
    gaps = []
    for s in obs["calls"]:
        ts = s.token_times
        for a, b in zip(ts, ts[1:]):
            if obs["drain"] or (lo <= a and b <= hi):
                gaps.append((b - a) * 1e3)
    return gaps
