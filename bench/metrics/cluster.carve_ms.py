"""95th percentile, over the jobs committed in the window, of the
program's ``cluster.carve`` span in the placement that committed each:
``ClusterState.candidate_subsets`` timed from inside (the program's
span; ``rm.carve_ms`` times the same call from the harness)."""
import numpy as np

import program_spans


def read(w):
    placed = program_spans.placements(w)
    if placed is None:
        return None
    return float(np.percentile([c.dur for _, _, c in placed], 95)) * 1e3
