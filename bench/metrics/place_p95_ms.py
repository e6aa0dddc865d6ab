"""95th percentile of placement delay over every job committed in the
window: wall time from the start of the ``step()`` pass that commits the
job to its commit (host clock)."""
import numpy as np


def read(w):
    if not w.latency_s:
        return None
    return float(np.percentile(w.latency_s, 95)) * 1e3
