"""From a profiler trace of the window to the numbers the metrics read.

``start``/``stop`` record the window with JAX's profiler (no Python tracer, so
the host runs as it does untraced but for the harness's own
``TraceAnnotation`` spans).  ``extract`` reads the ``.xplane.pb`` into
plain lists: the device operations of each TPU core, by short name, and
the harness's host spans.  ``summarize`` reduces those lists, and is what
the tests run on a small recorded trace (``bench/testdata``):

* busy: the union of device-operation intervals inside the harness's
  ``bench.window`` span, averaged over the chips;
* the idle gaps between them, each labelled by the innermost harness span
  that covers its middle (``rm.carve``, ``engine.wave``, ``rm.commit``,
  else ``rm.step``, else ``harness``), summed by label;
* device time by operation name, and the time in Pallas kernels
  (operations whose HLO is a ``tpu_custom_call``).
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional

WINDOW_SPAN = "bench.window"
SPANS = (WINDOW_SPAN, "rm.step", "rm.carve", "engine.wave", "rm.commit")
_INNER = ("rm.carve", "engine.wave", "rm.commit")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def op_name(hlo: str) -> str:
    """The short name of a device operation: the trace names each one by
    its whole HLO instruction text (``%fusion.3 = f32[...] fusion(...)``);
    the name is what stands before `` = ``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def extract(log_dir: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns, is_kernel], ...]},
    "spans": [[name, start_ns, dur_ns], ...]} from the newest trace.  A
    device operation is a Pallas kernel when its HLO is a
    ``tpu_custom_call``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, list] = {}
    spans: List[list] = []
    names: Dict[str, tuple] = {}        # HLO text -> (short name, is kernel)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    hlo = ev.name
                    if hlo not in names:
                        names[hlo] = (op_name(hlo), "tpu_custom_call" in hlo)
                    short, kernel = names[hlo]
                    ops.append([short, ev.start_ns, ev.duration_ns, kernel])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "spans": spans}


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


class _Labeller:
    """Which harness span covers a time: the innermost of ``_INNER``, else
    ``rm.step``, else ``harness``.  Spans of one name do not overlap, so a
    binary search over their starts finds the one that can cover it."""

    def __init__(self, spans: List[list]):
        self.levels = []
        for names in (_INNER, ("rm.step",)):
            iv = sorted((s[1], s[1] + s[2], s[0]) for s in spans
                        if s[0] in names)
            self.levels.append(([a for a, _, _ in iv], iv))

    def __call__(self, t: float) -> str:
        for starts, iv in self.levels:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t <= iv[i][1]:
                return iv[i][2]
        return "harness"


def summarize(trace: dict, top: int = 10) -> Optional[dict]:
    """Busy and window seconds, idle gaps by label, device time by op and
    in Pallas kernels.  None when the trace holds no window or no device
    operation."""
    win = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not win or not devices:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    label = _Labeller(trace["spans"])
    busy_ns, kernel_ns, gaps = 0.0, 0.0, {}
    by_op: Dict[str, float] = {}
    for ops in devices.values():
        inside = []
        for name, start, dur, kernel in ops:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            inside.append((a, b))
            by_op[name] = by_op.get(name, 0.0) + (b - a)
            if kernel:
                kernel_ns += b - a
        merged = _union(inside)
        busy_ns += sum(b - a for a, b in merged)
        edge = w0
        for a, b in merged + [(w1, w1)]:
            if a > edge:
                at = label((edge + a) / 2)
                gaps[at] = gaps.get(at, 0.0) + (a - edge)
            edge = max(edge, b)
    chips = len(devices)
    per_chip = 1e-9 / chips
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * per_chip,
        "kernel_s": kernel_ns * per_chip,
        "device_ops": sorted(([k, v * per_chip] for k, v in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([k, v * per_chip] for k, v in gaps.items()),
                            key=lambda x: -x[1])[:top],
    }
