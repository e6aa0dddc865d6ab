#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are named in
``BENCHMARK.json``; its metrics are the files of ``bench/metrics``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last ``TRACE_SECONDS`` and from the harness's own timers and
counters over the whole window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``busy_s``, ``window_s`` and a ``breakdown``), and last ``checks``,
each number the reference compared with its limit.  Those numbers are
also the last lines of standard error.  Context (samples, occupancy,
compiles) goes to standard error before them.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result.  JAX's persistent compilation cache lives in
``<checkout>/.bench_cache/jax``, so only the first run of a cell in a
checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE_DIR = CHECKOUT / ".bench_cache" / "jax"
TRACE_SECONDS = 5.0         # the profiler records the window's last seconds


def configure_jax() -> None:
    """The compile cache at its fixed path, caching every program however
    fast it compiled; the program's sources on the path.  Before JAX is
    imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    for p in (str(BENCH), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def read_metrics(names_units, window, bench: Path = BENCH) -> dict:
    """Each metric's reader, ``<bench>/metrics/<name>.py``; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for name, unit in names_units:
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{len(out)}", bench / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(window)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def checks(window, limits: dict) -> dict:
    c = window.checked
    got = {"alloc_faults": c.alloc_faults, "perm_faults": c.perm_faults,
           "above_identity": c.above_identity, "f_gap": c.f_gap,
           "degraded": window.degraded}
    return {k: {"value": v, "limit": limits[k]} for k, v in got.items()}


def tpu_devices(chips: int):
    """The devices, or None (with the reason on stderr) when JAX finds no
    TPU, fewer than ``chips``, or a chip ``peaks`` does not know."""
    import jax
    import peaks
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no accelerator: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    try:
        peaks.peaks(devices[0].device_kind)     # bounds hold for known chips
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    configure_jax()
    import harness
    import traffic
    import trace_reduce

    cell = harness.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    if devices is None:
        return 2
    devices = devices[:cell.chips]
    meter = harness.CompileMeter()
    M = harness.machine(cell.config)
    t_warm = time.perf_counter()
    warmed = harness.warm(cell.config,
                          traffic.Stream(cell.config, cell.mix, args.seed))
    warm_s = time.perf_counter() - t_warm

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        w = harness.run_window(
            cell.config, cell.mix, M, args.seed, args.seconds, meter,
            traced=bool(args.trace),
            trace_start=(lambda: trace_reduce.start(trace_dir)) if args.trace
            else None,
            trace_stop=trace_reduce.stop if args.trace else None,
            trace_seconds=TRACE_SECONDS)
        w.setup_s = w.t_open - T_START
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        if args.trace:
            w.trace = trace_reduce.summarize(trace_reduce.extract(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    limits = cell.config["limits"]
    harness.check(w, M, limits)

    setup_compiles, _ = meter.between(T_START, w.t_open)
    err = sys.stderr
    first = meter.cache_loads < len(setup_compiles)
    print(f"setup: {w.setup_s} s; {len(setup_compiles)} programs compiled or "
          f"loaded ({meter.cache_loads} from the persistent cache"
          f"{', first run in this checkout' if first else ''}); "
          f"warming {warm_s} s ({warmed} programs warmed by shape); "
          f"fill and priming {w.fill_s} s ({w.fill_placements} placements "
          f"by the identity stand-in, {cell.mix['prime_placements']} by "
          f"the engine)", file=err)
    print(f"window open: occupancy {w.occupancy_open}, {w.running_open} jobs "
          f"running, {w.queued_open} queued", file=err)
    print(f"window: {w.window_s} s, {w.placements} placements (the p95 "
          f"sample count), {len(w.compiles)} compiles "
          f"{sorted(set(w.compiles))}, {w.traces} traces; engine "
          f"{w.engine_stats}", file=err)
    print(f"cost: mapped F {w.checked.mapped_f}, identity F "
          f"{w.checked.identity_f}", file=err)
    for job_id, fault in list(w.checked.faults.items())[:10]:
        print(f"fault: {job_id}: {fault}", file=err)

    group = cell.per_layer if args.trace else cell.end_to_end
    metrics = read_metrics([(m["name"], m["unit"]) for m in group], w)
    compared = checks(w, limits)
    correct = w.placements > 0 and all(
        v["value"] <= v["limit"] for v in compared.values())
    failed = len(w.checked.faults) + w.degraded
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": w.placements,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and w.trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        result["breakdown"] = {"device_ops": w.trace["device_ops"],
                               "idle_gaps": w.trace["idle_gaps"]}
    result["checks"] = compared
    for k, v in compared.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=err)
    err.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
