"""95th percentile, over the jobs committed in the window, of the host
time of the ``ClusterState.candidate_subsets`` call that carved their
candidates (the harness's timer around the call)."""
import numpy as np


def read(w):
    if not w.carve_s:
        return None
    return float(np.percentile(w.carve_s, 95)) * 1e3
