#!/usr/bin/env python3
"""Readings for the correctness limits: many seeds in one process.

    python3 bench/study.py --workload <cell> --seconds <s> --seeds <n> ...

The cell's programs are warmed once; then each seed gets a fresh resource
manager and engine on its own stream, fills the machine, runs its window
and prints one JSON line: the numbers the reference compared for the
program's answers, and the F gap of the control (the reference's objective
in bfloat16 put in the program's place, ``control.py``) on the same jobs.
The benchmark's own runs never run the control.  Needs a TPU, as
``run.py`` does.
"""
import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    run.configure_jax()
    import control
    import harness
    import traffic

    cell = harness.load_cell(args.workload)
    if run.tpu_devices(cell.chips) is None:
        return 2
    meter = harness.CompileMeter()
    M = harness.machine(cell.config)
    harness.warm(cell.config, traffic.Stream(cell.config, cell.mix, args.seeds[0]))
    for seed in args.seeds:
        w = harness.run_window(cell.config, cell.mix, M, seed, args.seconds,
                               meter)
        harness.check(w, M, cell.config["limits"])
        print(json.dumps({
            "seed": seed, "placements": w.placements,
            "compiles": len(w.compiles),
            "program": {k: v["value"] for k, v in
                        run.checks(w, cell.config["limits"]).items()},
            "control": control.readings(w, M),
            "faults": list(w.checked.faults.values())[:5],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
