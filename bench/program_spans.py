"""The program's own spans over a run's window (``repro.serve.telemetry``),
for the readers of the metrics that read them.

The ring's records that start inside the window, ``[t_open, t_open +
window_s]`` on ``perf_counter``, joined to the window's committed jobs by
their ``job`` attribute.  A program without the ring (an older checkout)
reads nothing: every function here then returns None, and so does every
reader."""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional


def window(w, names: Iterable[str]) -> Optional[Dict[str, list]]:
    """{name: records} of the window, for each of ``names``; None when the
    program keeps no spans."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    names = tuple(names)
    by = {n: [] for n in names}
    for r in telemetry.spans(w.t_open, w.t_open + w.window_s, names):
        by[r.name].append(r)
    return by


def inside(outer, t: float) -> bool:
    return outer.t0 <= t <= outer.t0 + outer.dur


def placements(w) -> Optional[List[tuple]]:
    """For each job committed in the window, the spans of the placement
    that committed it: (its ``rm.pass``, ``rm.place``, ``cluster.carve``).
    The committing ``rm.place`` is the job's one that holds the job's
    ``cluster.promote``."""
    by = window(w, ("rm.pass", "rm.place", "cluster.carve",
                    "cluster.promote"))
    if by is None:
        return None
    jobs = {c.job_id for c in w.commits if c.in_window}
    out = []
    for pr in by["cluster.promote"]:
        job = pr.attrs["job"]
        place = next((p for p in by["rm.place"]
                      if p.attrs["job"] == job and inside(p, pr.t0)), None)
        if job not in jobs or place is None:
            continue
        carve = next((c for c in by["cluster.carve"]
                      if c.attrs.get("job") == job and inside(place, c.t0)),
                     None)
        pas = next((p for p in by["rm.pass"] if inside(p, place.t0)), None)
        if carve is not None and pas is not None:
            out.append((pas, place, carve))
    return out or None
