"""Jobs committed in the window over the window's wall seconds (host
clock), the last pass included whole."""


def read(w):
    if not w.latency_s:
        return None
    return len(w.latency_s) / w.window_s
