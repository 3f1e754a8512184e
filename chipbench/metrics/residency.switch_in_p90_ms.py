"""90th percentile of the switch-in time of the window's calls, as the
service records it per call (``switch_s``: reclaim, disk reads and
recompute of chunks that are not resident)."""
from chipbench.readlib import pct


def read(obs):
    return pct([s.switch_s * 1e3 for s in obs["calls"]
                if s.switch_s is not None], 90)
