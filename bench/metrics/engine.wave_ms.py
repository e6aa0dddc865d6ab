"""Median over the window's waves (one per committed job) of the
program's own wave timer, ``JobHandle.map_wall_s``: submit to results of
the K candidates' mapping wave."""
import numpy as np


def read(w):
    if not w.wave_s:
        return None
    return float(np.median(w.wave_s)) * 1e3
