"""The metrics that read the program's own spans: a traced run of the
whole harness on the CPU at a tiny size reads each of them, and a program
without the span ring reads none of them."""
import json
import math
import sys

import pytest

import harness
import run
from conftest import BENCH

SEED = 2**33 + 4321
SPAN_METRICS = {"cluster.carve_ms", "rm.wait_ms", "engine.host_ms",
                "engine.useful_share", "solver.rounds", "solver.live_share"}


def run_main(monkeypatch, capsys, cfg, mix, trace):
    """``bench/run.py``'s whole run on the CPU, with the look for a chip
    skipped and the cell cut to ``cfg``; returns (exit code, result line,
    stderr)."""
    import jax
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = harness.Cell(name="tiny.steady", config=cfg, mix=mix, chips=1,
                        end_to_end=spec["end_to_end"],
                        per_layer=spec["per_layer"])
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "tpu_devices",
                        lambda chips: jax.devices()[:chips])
    rc = run.main(["--workload", "tiny.steady", "--seed", str(SEED),
                   "--seconds", "1.5", "--trace", str(trace)])
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_traced_run_reads_every_span_metric(tiny_config, tiny_mix,
                                            monkeypatch, capsys):
    rc, result, err = run_main(monkeypatch, capsys, tiny_config, tiny_mix,
                               trace=1)
    assert rc == 0 and result["correct"], err[-2000:]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert SPAN_METRICS <= set(got), sorted(got)
    assert all(math.isfinite(got[k]) for k in SPAN_METRICS)
    window_ms = 1e3 * 1.5 * 2
    assert 0 < got["cluster.carve_ms"] < window_ms
    assert 0 <= got["rm.wait_ms"] < window_ms
    assert 0 < got["engine.host_ms"] < window_ms
    assert 0 < got["engine.useful_share"] <= 100
    assert 0 < got["solver.live_share"] <= 100
    sa = tiny_config["engine"]["sa_cfg"]
    levels = sa.num_exchanges * sa.iters_per_exchange
    assert levels <= got["solver.rounds"] <= levels * (
        min(sa.max_success, sa.max_neighbors) + sa.max_neighbors)
    units = {m["name"]: m["unit"] for m in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]}
    assert all(result["metrics"][k]["unit"] == units[k]
               for k in SPAN_METRICS)


def test_a_program_without_spans_reads_none_of_them(tiny_config, tiny_mix,
                                                   monkeypatch):
    """An older checkout has no ``repro.serve.telemetry``: the readers
    leave their metrics out instead of raising."""
    import repro.serve
    meter = harness.CompileMeter()
    M = harness.machine(tiny_config)
    w = harness.run_window(tiny_config, tiny_mix, M, SEED, 0.5, meter)
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    monkeypatch.delattr(repro.serve, "telemetry")
    with pytest.raises(ImportError):
        from repro.serve import telemetry  # noqa: F401
    assert run.read_metrics([(m, "x") for m in sorted(SPAN_METRICS)],
                            w) == {}
