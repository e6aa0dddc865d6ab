"""95th percentile, over the jobs committed in the window, of the wait
inside the scheduling pass before the job's own placement began: the
start of its committing ``rm.place`` span minus the start of the
``rm.pass`` span around it (the program's spans).  A job waits there
behind the placements its pass made first."""
import numpy as np

import program_spans


def read(w):
    placed = program_spans.placements(w)
    if placed is None:
        return None
    return float(np.percentile([pl.t0 - ps.t0 for ps, pl, _ in placed],
                               95)) * 1e3
