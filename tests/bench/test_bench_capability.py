"""The capability cell: it loads from its files, and its whole traced run
on the CPU, cut to a 128-node machine and jobs of 16 to 48 processes that
take the multilevel path, is correct and reads every per-layer metric
that applies to it."""
import json
import math

from dataclasses import replace

import harness
import run
from conftest import BENCH
from repro.core.annealing import SAConfig
from repro.core.multilevel import MultilevelConfig
from repro.serve import MappingEngine
from repro.serve import telemetry

CELL = "capability4096-psa.steady"
SEED = 2**33 + 2718
SA = SAConfig(max_neighbors=4, iters_per_exchange=2, num_exchanges=2,
              solvers=2)
# the profiler's device trace holds TPU operations only: no CPU reading
DEVICE_TRACE = {m["name"] for m in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
    if m["source"] == "device_trace"}


def small(cell: harness.Cell) -> harness.Cell:
    """The cell cut in the test: 128 nodes, jobs of 16 to 48 (all above
    the one dense bucket, so every job is multilevel), token budgets."""
    cfg = json.loads(json.dumps(cell.config))
    cfg["machine"]["dims"] = [4, 4, 8]
    cfg["jobs"]["size_model"].update(min_size=16, max_size=48)
    cfg["jobs"]["block_jobs"] = 24
    cfg["engine"] = {
        "buckets": [8], "large_buckets": [512], "multilevel_min_n": 16,
        "polish_rounds": 8, "warm_start": True, "sa_cfg": SA,
        "multilevel_cfg": MultilevelConfig(
            coarse_n=8, coarse_sa=SA, refine_sa=replace(SA, flows="sparse"),
            final_polish_rounds=8)}
    mix = dict(cell.mix, backlog=4, prime_placements=4)
    return replace(cell, config=cfg, mix=mix)


def test_the_cell_loads_from_its_files():
    cell = harness.load_cell(CELL)
    assert cell.config["name"] == "capability4096-psa"
    assert cell.config["jobs"]["size_model"]["min_size"] == 256
    assert cell.config["jobs"]["size_model"]["max_size"] == 2048
    assert cell.config["reduced"] == [] and cell.chips == 1
    assert cell.mix == json.loads((BENCH / "mixes" / "steady.json")
                                  .read_text())
    names = {m["name"] for m in cell.per_layer}
    assert {"ml.coarsen_ms", "ml.host_share"} <= names
    capacity = harness.load_cell("capacity4096-psa.steady")
    assert not {"ml.coarsen_ms", "ml.host_share"} & {
        m["name"] for m in capacity.per_layer}
    for key in ("machine", "rm", "flows", "limits"):
        assert cell.config[key] == capacity.config[key]
    # the same engine, with the multilevel settings it runs written out
    engine = dict(cell.config["engine"])
    ml = engine.pop("multilevel_cfg")
    assert engine == capacity.config["engine"]
    assert MappingEngine(**cell.config["engine"]).multilevel_cfg == (
        MultilevelConfig(**ml))
    assert ml["coarse_n"] == 64


def test_a_small_capability_window_is_correct_and_reads_every_metric(
        monkeypatch, capsys):
    import jax
    cell = small(harness.load_cell(CELL))
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "tpu_devices",
                        lambda chips: jax.devices()[:chips])
    t0 = telemetry.spans(0, float("inf"))
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "2", "--trace", "1"])
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"], err[-2000:]
    assert all(v["value"] == 0 for v in result["checks"].values())
    got = {k: v["value"] for k, v in result["metrics"].items()}
    want = {m["name"] for m in cell.per_layer} - DEVICE_TRACE
    assert want <= set(got), sorted(want - set(got))
    assert all(math.isfinite(got[k]) for k in want)
    assert 0 < got["ml.host_share"] <= 100
    assert 0 < got["ml.coarsen_ms"] < 4e3
    assert 0 < got["engine.useful_share"] <= 100
    # every solve coarsened to the configured coarse order
    coarse_n = cell.config["engine"]["multilevel_cfg"].coarse_n
    solves = [r.attrs for r in telemetry.spans(0, float("inf"),
                                               ("ml.coarsen",))
              if r not in t0]
    assert solves and all(a["coarse_n"] <= coarse_n and a["levels"] >= 1
                          for a in solves)
