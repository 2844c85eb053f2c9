"""95th percentile of all gaps between consecutive tokens of one request,
both tokens inside the window, pooled over every request (host clock), in
cells whose window admits requests: its tail is set by the decode steps
that wait on an admission's prefill."""
import numpy as np

from chipbench.drive import itl_gaps


def read(run):
    gaps = itl_gaps(run.window)
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
