"""Share of the multilevel solves' time the host spends outside waits
for the device: 100 x the sum over the window's multilevel
``engine.dispatch`` spans of (duration - the ``engine.fetch`` spans
inside it), over the sum of their durations (the program's spans)."""
import program_spans


def read(w):
    by = program_spans.window(w, ("engine.dispatch", "engine.fetch"))
    solves = [d for d in by["engine.dispatch"]
              if d.attrs.get("path") == "multilevel"] if by else []
    total = sum(d.dur for d in solves)
    if total <= 0:
        return None
    waits = sum(f.dur for d in solves for f in by["engine.fetch"]
                if program_spans.inside(d, f.t0))
    return 100.0 * (total - waits) / total
