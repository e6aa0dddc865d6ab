"""Program spans: where a placement spends its time, from the resource
manager's pass down to the solver's event loop.

``span(name, **attrs)`` times a block of the program twice over, on one
clock each reader already has:

* as a ``jax.profiler.TraceAnnotation``: with the profiler attached the
  span sits in the host plane of the same xplane as the device
  operations, its attributes as event stats (the profiler's encoding
  reserves ``#`` and ``,``, so string values carry ``:`` and `` ``
  there instead; a request id ``j7#c0`` reads ``j7:c0``);
* as a record ``(name, t0, dur, attrs)`` in a bounded, process-wide ring,
  always on, ``t0`` from ``time.perf_counter()``.  ``spans(t0, t1)``
  reads the records that started in a window.

Attributes known only at a span's end are added with ``Span.set``.  A
span that carries ``job=`` makes that job the current one for the spans
it encloses on the same thread (``current_job()``); attributes that are
None are left out.  The spans of the placement path, and how to read
them: docs/DESIGN.md §14.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import threading
import time
from typing import Iterable, Iterator, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

RING_SIZE = 65536

_ring: "collections.deque[Record]" = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()
_job: contextvars.ContextVar = contextvars.ContextVar("job", default=None)


class Record(NamedTuple):
    name: str
    t0: float           # time.perf_counter() at the span's start
    dur: float          # seconds
    attrs: dict


def _stat(v):
    if isinstance(v, (list, tuple)):
        v = " ".join(map(str, v))
    if isinstance(v, str):
        return v.replace("#", ":").replace(",", " ")
    return v


class Span:
    """An open span; ``dur`` is set when it closes."""

    __slots__ = ("name", "attrs", "t0", "dur", "_note")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.dur = 0.0
        self._note = TraceAnnotation(
            name, **{k: _stat(v) for k, v in attrs.items()})

    def set(self, **attrs) -> None:
        attrs = {k: v for k, v in attrs.items() if v is not None}
        self.attrs.update(attrs)
        self._note.set_metadata(**{k: _stat(v) for k, v in attrs.items()})


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Span]:
    s = Span(name, {k: v for k, v in attrs.items() if v is not None})
    job = s.attrs.get("job")
    token = _job.set(job) if job is not None else None
    with s._note:
        s.t0 = time.perf_counter()
        try:
            yield s
        finally:
            s.dur = time.perf_counter() - s.t0
            if token is not None:
                _job.reset(token)
            with _ring_lock:
                _ring.append(Record(s.name, s.t0, s.dur, s.attrs))


def current_job() -> Optional[str]:
    """The job of the innermost enclosing span that names one."""
    return _job.get()


def spans(t0: float, t1: float,
          names: Optional[Iterable[str]] = None) -> List[Record]:
    """The ring's records that started in ``[t0, t1]`` (perf_counter
    seconds), in the order they closed; only those named in ``names``
    when given."""
    keep = None if names is None else frozenset(names)
    with _ring_lock:
        recs = list(_ring)
    return [r for r in recs if t0 <= r.t0 <= t1
            and (keep is None or r.name in keep)]
