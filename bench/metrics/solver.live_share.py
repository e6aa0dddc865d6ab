"""Share of the batched event loop's lane rounds in which the lane still
had work, over the window's bucket PSA waves: sum of ``lane_rounds`` over
sum of ``lanes`` x ``rounds_executed`` (the ``engine.fetch`` span's
counters, counted on the device).  The rest are rounds a lane idled
while a slower lane of its level finished."""
import program_spans


def read(w):
    by = program_spans.window(w, ("engine.fetch",))
    runs = [f.attrs for f in by["engine.fetch"]
            if "rounds_executed" in f.attrs] if by else []
    if not runs:
        return None
    return 100.0 * sum(a["lane_rounds"] for a in runs) / sum(
        a["lanes"] * a["rounds_executed"] for a in runs)
