"""The harness's whole run on the CPU at a tiny size: the metric
arithmetic under an injected stall, the faults the reference must catch
when the timed path is broken underneath, and the control."""
import json
import time

import numpy as np
import pytest

import control
import harness
import reference
import run
import traffic
from conftest import BENCH
from repro.serve import ClusterState, MappingEngine

SEED = 2**33 + 12345           # seeds may exceed 32 bits


@pytest.fixture(scope="module")
def warmed():
    from conftest import tiny
    cfg = tiny()
    meter = harness.CompileMeter()
    M = harness.machine(cfg)
    harness.warm(cfg, traffic.Stream(cfg, {"backlog": 4}, SEED))
    return meter, M


def window(cfg, mix, warmed, seconds=1.5, seed=SEED, **trace):
    meter, M = warmed
    w = harness.run_window(cfg, mix, M, seed, seconds, meter, **trace)
    harness.check(w, M, cfg["limits"])
    return w


def read(name, w):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m", harness.BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(w)


def run_main(monkeypatch, capsys, cfg, mix, trace=0):
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


def test_sound_run_is_correct_and_compiles_nothing(tiny_config, tiny_mix,
                                                   warmed, monkeypatch,
                                                   capsys):
    rc, result, err = run_main(monkeypatch, capsys, tiny_config, tiny_mix)
    assert rc == 0 and result["correct"], err[-2000:]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"place_p95_ms", "placements_per_s",
                                      "cost_ratio", "setup_s"}
    assert 0 < result["metrics"]["cost_ratio"]["value"] <= 1
    assert result["attempted"] > 10 and result["failed"] == 0
    assert result["device"]["count"] == 1
    # the numbers compared, each beside its limit, close standard error
    last = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split(":")[0] for line in last] == [
        f"check {k}" for k in result["checks"]]
    window_line = [line for line in err.splitlines()
                   if line.startswith("window: ")]
    assert ", 0 compiles [], 0 traces" in window_line[0], window_line
    w = window(tiny_config, tiny_mix, warmed)
    assert w.occupancy_open >= tiny_mix["min_occupancy"]
    assert w.queued_open == tiny_mix["backlog"]


def test_traced_window_times_the_whole_window(tiny_config, tiny_mix, warmed):
    """The profiler covers only the window's last seconds, starting that
    long before it closes and stopping after; the harness's own timers
    count every placement of the window."""
    at = {}
    w = window(tiny_config, tiny_mix, warmed, seconds=2.0,
               trace_start=lambda: at.setdefault("start", time.perf_counter()),
               trace_stop=lambda: at.setdefault("stop", time.perf_counter()),
               trace_seconds=0.5)
    close = w.t_open + w.window_s
    assert w.t_open + 1.5 <= at["start"] <= close <= at["stop"]
    assert len(w.carve_s) == len(w.wave_s) == w.placements > 10


def test_stall_moves_p95_and_rate(tiny_config, tiny_mix, warmed,
                                  monkeypatch):
    """Every 4th carve sleeps: the tail over all jobs and the rate over the
    whole window both show it."""
    base = window(tiny_config, tiny_mix, warmed)
    calls = []
    orig = ClusterState.candidate_subsets

    def slow(self, *a, **kw):
        calls.append(1)
        if len(calls) % 4 == 0:
            time.sleep(0.05)
        return orig(self, *a, **kw)

    monkeypatch.setattr(ClusterState, "candidate_subsets", slow)
    stalled = window(tiny_config, tiny_mix, warmed)
    assert read("place_p95_ms", stalled) >= 50
    assert read("place_p95_ms", stalled) > read("place_p95_ms", base)
    assert read("placements_per_s", stalled) < read("placements_per_s", base)
    assert read("rm.carve_ms", stalled) >= 50


def _broken_solve(kind):
    orig = MappingEngine._solve_bucket

    def solve(self, bucket, algorithm, tier, reqs, warms):
        out = orig(self, bucket, algorithm, tier, reqs, warms)
        if kind == "half_left_out":
            # the first half of the wave takes the second half's answers
            h = len(out) // 2
            return out[len(out) - h:] + out[h:]
        # an answer altered where it is produced
        return [(np.concatenate([p[1::-1], p[2:]]), f) for p, f in out]
    return solve


def _unchanged_commit():
    """From the window on, a commit records the allocation but leaves the
    cluster's occupancy as it was: a step that returns its state
    unchanged."""
    orig = ClusterState._commit

    def commit(self, job_id, nodes):
        free = self._free.copy()
        alloc = orig(self, job_id, nodes)
        if getattr(self, "rec", None) is not None and self.rec.in_window:
            self._free[:] = free
        return alloc
    return commit


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "altered"])
def test_faults_make_the_run_incorrect(tiny_config, tiny_mix, warmed,
                                       monkeypatch, capsys, fault):
    """The whole run, with the timed path broken underneath, reports
    ``correct`` false and names the number that caught it."""
    if fault == "state_unchanged":
        monkeypatch.setattr(ClusterState, "_commit", _unchanged_commit())
        caught = "alloc_faults"
    else:
        monkeypatch.setattr(MappingEngine, "_solve_bucket",
                            _broken_solve(fault))
        caught = "f_gap"
    rc, result, err = run_main(monkeypatch, capsys, tiny_config, tiny_mix)
    assert rc == 0 and not result["correct"]
    c = result["checks"][caught]
    assert c["value"] > c["limit"] and result["failed"] > 0


def test_control_in_lower_precision_is_incorrect(tiny_config, tiny_mix,
                                                 warmed):
    """The reference's objective computed in bfloat16, put in place of the
    program's answers, fails the exact F comparison; the program's own
    answers pass it."""
    w = window(tiny_config, tiny_mix, warmed)
    assert w.checked.f_gap == 0.0
    readings = control.readings(w, harness.machine(tiny_config))
    assert readings["f_gap"] > tiny_config["limits"]["f_gap"]


def test_reference_flags_a_corrupted_permutation_and_a_wrong_f():
    M = reference.grid_distances((2, 2, 2))
    C = traffic.ring_background_flows(4, 7)
    nodes = np.array([0, 3, 5, 6])
    commit = reference.Commit("a", 4, 1.0, 0.0, nodes, in_window=True)
    perm = np.array([2, 0, 3, 1])
    f = reference.objective(C, M, nodes, perm)
    ok = reference.check([commit], {"a": (perm, f)}, lambda j: C, M)
    assert ok.faults == {} and ok.f_gap == 0.0
    bad_perm = reference.check([commit], {"a": (np.array([2, 0, 2, 1]), f)},
                               lambda j: C, M)
    assert bad_perm.perm_faults == 1
    wrong_f = reference.check([commit], {"a": (perm, f + 1)}, lambda j: C, M)
    assert wrong_f.f_gap > 0 and "a" in wrong_f.faults


def test_reference_flags_overlapping_and_misfit_allocations():
    def c(job, size, clock, nodes, run_s=10.0):
        return reference.Commit(job, size, run_s, clock, np.array(nodes))
    # b overlaps a while a runs; d starts after a finished: no fault
    commits = [c("a", 2, 0.0, [0, 1]), c("b", 2, 1.0, [1, 2]),
               c("d", 2, 10.0, [0, 3]), c("e", 3, 11.0, [4, 5])]
    faults = reference.allocation_faults(commits, 8)
    assert set(faults) == {"b", "e"}
