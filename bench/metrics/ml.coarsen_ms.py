"""Median over the window's ``ml.coarsen`` spans of their duration: the
host work of one multilevel solve before its first device program, the
matchings and contractions of every level and their conversion to the
sparse form (the program's spans)."""
import numpy as np

import program_spans


def read(w):
    by = program_spans.window(w, ("ml.coarsen",))
    if not by or not by["ml.coarsen"]:
        return None
    return float(np.median([s.dur for s in by["ml.coarsen"]])) * 1e3
