"""Process start to window open: imports, warming the cell's programs
(compiles or cache loads included) and driving the stream to its steady
state (host clock)."""


def read(w):
    return w.setup_s
