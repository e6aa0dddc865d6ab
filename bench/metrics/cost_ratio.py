"""Sum of mapped F over sum of the identity's F on the same nodes, over
the jobs committed in the window, both recomputed by the reference in
float64."""


def read(w):
    if w.checked.identity_f <= 0:
        return None
    return w.checked.cost_ratio
