"""95th percentile of every gap between consecutive tokens of a call,
over all window calls.  Where the cell stops at the window's end (no
drain), only gaps whose two tokens both landed inside the window."""
from chipbench.readlib import pct, token_gaps_ms


def read(obs):
    return pct(token_gaps_ms(obs), 95)
