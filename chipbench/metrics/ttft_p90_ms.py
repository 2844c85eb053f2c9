"""90th percentile, over every request whose first token falls in the
window, of its first token's time minus the time of the completion that
released it (closed loop, host clock)."""
import numpy as np

from chipbench.drive import ttfts


def read(run):
    t = ttfts(run.window, run.lanes)
    if not t:
        return None
    return float(np.percentile(t, 90)) * 1e3
