"""Share of the rows the delta evaluation works on that belong to real
processes, over the window's bucket waves: sum of the waves' ``orders``
over sum of ``padded_rows`` x ``kernel_order`` (the ``engine.pack``
span's counters: the instances padded to the bucket, the wave to a power
of two, and the bucket to whole lanes on the kernel path)."""
import program_spans


def read(w):
    by = program_spans.window(w, ("engine.pack",))
    if not by or not by["engine.pack"]:
        return None
    packs = [p.attrs for p in by["engine.pack"]]
    return 100.0 * sum(a["orders"] for a in packs) / sum(
        a["padded_rows"] * a["kernel_order"] for a in packs)
