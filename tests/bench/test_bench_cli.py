"""The command's contract: no TPU, no result; unknown chips refused; new
cells, mixes, configurations and metrics are files."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness
import peaks
import run
from conftest import BENCH

CHECKOUT = BENCH.parent


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "capacity4096-psa.steady", "--seed", str(2**33), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_every_cell_names_files_that_exist():
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["backlog"] >= 1
        for m in cell.end_to_end + cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_benchmark_json_keeps_to_its_schema():
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (CHECKOUT / p).is_dir()
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (CHECKOUT / c["file"]).is_file()
    assert len({c["source"] for c in spec["configs"]}) == len(configs)
    assert {w["config"] for w in spec["workloads"]} == set(configs)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
    texts = [w["why"] for w in spec["workloads"]] + [
        c[k] for c in spec["configs"] for k in ("why", "source")]
    assert all(0 < len(t) <= 200 and "\n" not in t for t in texts)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in spec["workloads"]:
        assert NAME.fullmatch(w["name"]) and w["chips"] in (1, 4)


def test_a_new_mix_config_and_metric_are_new_files_only(tmp_path):
    """A cell with its own mix, configuration and per-layer metric needs
    entries in BENCHMARK.json and new files; no file of the harness
    changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.py")}
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    (root / "bench" / "mixes" / "bursty.json").write_text(json.dumps(
        dict(json.loads((BENCH / "mixes" / "steady.json").read_text()),
             name="bursty", backlog=64)))
    cfg = json.loads((BENCH / "configs" / "capacity4096-psa.json").read_text())
    cfg["name"] = "capacity4096-pga"
    cfg["rm"]["algorithm"] = "pga"
    (root / "bench" / "configs" / "capacity4096-pga.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "metrics" / "rm.fill_placements.py").write_text(
        "def read(w):\n    return float(w.fill_placements)\n")
    spec["configs"].append(dict(spec["configs"][0], name="capacity4096-pga",
                                file="bench/configs/capacity4096-pga.json"))
    spec["workloads"].append({"name": "capacity4096-pga.bursty",
                              "config": "capacity4096-pga",
                              "traffic": "bursty", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "rm.fill_placements", "unit": "jobs",
                              "better": "lower", "source": "host_clock",
                              "layer": "control plane",
                              "moves": "place_p95_ms",
                              "workloads": ["capacity4096-pga.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("capacity4096-pga.bursty",
                             spec_path=root / "BENCHMARK.json",
                             bench=root / "bench")
    assert cell.config["rm"]["algorithm"] == "pga"
    assert cell.mix["backlog"] == 64
    assert "rm.fill_placements" in [m["name"] for m in cell.per_layer]
    got = run.read_metrics([("rm.fill_placements", "jobs")],
                           type("W", (), {"fill_placements": 7})(),
                           bench=root / "bench")
    assert got == {"rm.fill_placements": {"value": 7.0, "unit": "jobs"}}
    assert before == {p: p.read_bytes() for p in (root / "bench").rglob("*.py")
                      if p in before}
