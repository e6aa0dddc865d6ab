"""Median over the window's bucket PSA waves of ``rounds_executed``: the
sequential rounds the batched acceptance-event loop ran, at each
temperature level as many as its slowest lane (the ``engine.fetch``
span's counters, counted on the device)."""
import numpy as np

import program_spans


def read(w):
    by = program_spans.window(w, ("engine.fetch",))
    runs = [f.attrs["rounds_executed"] for f in by["engine.fetch"]
            if "rounds_executed" in f.attrs] if by else []
    if not runs:
        return None
    return float(np.median(runs))
