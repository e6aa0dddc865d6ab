"""Median over the window's ``engine.flush`` spans of the flush's host
time: its duration minus the ``engine.fetch`` spans inside it, the waits
for the device's results (the program's spans).  What is left is cache
work, packing, enqueueing and responding."""
import numpy as np

import program_spans


def read(w):
    by = program_spans.window(w, ("engine.flush", "engine.fetch"))
    if not by or not by["engine.flush"]:
        return None
    host = [f.dur - sum(x.dur for x in by["engine.fetch"]
                        if program_spans.inside(f, x.t0))
            for f in by["engine.flush"]]
    return float(np.median(host)) * 1e3
