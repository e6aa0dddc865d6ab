"""Share of the device's busy time inside the window spent in Pallas
kernels (operations whose HLO is a ``tpu_custom_call``), from the
profiler trace."""


def read(w):
    t = w.trace
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
