"""90th percentile of the time from a call's due time until the router
began it: the router's admission wait (``call_records`` ``wait_s``) plus
the generator's lag in submitting it."""
from chipbench.readlib import pct


def read(obs):
    return pct([(s.t_begin - s.due) * 1e3 for s in obs["calls"]
                if s.t_begin is not None], 90)
