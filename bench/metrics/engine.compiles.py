"""Programs compiled or loaded from the persistent cache inside the
window (JAX's backend-compile monitoring events).  Set-up warms every
program the cell's stream dispatches, so anything here is a program that
compiled while users waited."""


def read(w):
    return float(len(w.compiles))
