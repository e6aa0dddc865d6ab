"""Program spans: the ring and its window filter, and a resource-manager
replay whose spans reach the profiler's host plane, nested and joined by
job id."""
import glob
import os
import time

import numpy as np
import jax

from repro.core.annealing import SAConfig
from repro.serve import JobSpec, MappingEngine, ResourceManager, telemetry

TABLE = ("rm.pass", "rm.place", "cluster.carve", "rm.wave",
         "cluster.promote", "engine.flush", "engine.pack", "engine.dispatch",
         "engine.fetch", "engine.respond")
SA = SAConfig(max_neighbors=4, iters_per_exchange=2, num_exchanges=2,
              solvers=2)


def test_window_filter_names_and_late_attributes():
    t0 = time.perf_counter()
    with telemetry.span("test.outer", job="a1", size=3, skip=None) as outer:
        assert telemetry.current_job() == "a1"
        with telemetry.span("test.inner"):
            assert telemetry.current_job() == "a1"
        outer.set(done=2)
    assert telemetry.current_job() is None
    t1 = time.perf_counter()
    with telemetry.span("test.outer"):
        pass
    got = telemetry.spans(t0, t1)
    assert [r.name for r in got] == ["test.inner", "test.outer"]
    assert got[1].attrs == {"job": "a1", "size": 3, "done": 2}
    assert t0 <= got[1].t0 <= got[0].t0
    assert got[0].t0 + got[0].dur <= got[1].t0 + got[1].dur <= t1
    assert [r.name for r in telemetry.spans(t0, t1, ["test.inner"])] == [
        "test.inner"]
    assert len(telemetry.spans(t0, time.perf_counter(), ["test.outer"])) == 2


def test_ring_is_bounded():
    t0 = time.perf_counter()
    for i in range(telemetry.RING_SIZE + 5):
        with telemetry.span("test.fill", i=i):
            pass
    got = telemetry.spans(t0, time.perf_counter())
    assert len(got) == telemetry.RING_SIZE
    assert got[0].attrs["i"] == 5
    assert got[-1].attrs["i"] == telemetry.RING_SIZE + 4


def _replay():
    n = 64
    xyz = np.stack(np.unravel_index(np.arange(n), (4, 4, 4)), 1)
    M = np.abs(xyz[:, None] - xyz[None]).sum(-1).astype(np.float32)
    eng = MappingEngine(buckets=(16,), polish_rounds=4, sa_cfg=SA)
    rm = ResourceManager(M, eng, candidates=3)
    handles = [rm.submit_job(JobSpec(job_id=f"j{i}", size=2 + i % 13,
                                     run_s=1.0 + i % 3, seed=i))
               for i in range(14)]
    rm.run()
    return handles


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in TABLE:
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return out


def test_replay_spans_reach_the_host_plane_nested_by_job(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handles = _replay()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert {e[0] for e in events} == set(TABLE)
    by = {name: [e for e in events if e[0] == name] for name in TABLE}
    places = by["rm.place"]
    assert all(e[3]["job"].startswith("j") for e in places)
    for name in ("cluster.carve", "rm.wave", "cluster.promote"):
        for _, a, b, stats in by[name]:
            inside = [p for p in places if p[1] <= a and b <= p[2]
                      and p[3]["job"] == stats["job"]]
            assert len(inside) == 1, (name, stats)
    # a committed job's promote lies in its place; request ids keep their
    # job, with the profiler's reserved '#' read as ':'
    assert {e[3]["job"] for e in by["cluster.promote"]} == {
        h.job_id for h in handles}
    assert "j0:c0" in by["engine.flush"][0][3]["jobs"].split()

    # the ring holds the same spans, on perf_counter, with the raw ids
    ring = telemetry.spans(t0, time.perf_counter(), TABLE)
    assert sorted(r.name for r in ring) == sorted(e[0] for e in events)
    waves = {r.attrs["job"]: r for r in ring if r.name == "rm.wave"}
    for h in handles:
        assert h.map_wall_s == waves[h.job_id].dur
    flush = next(r for r in ring if r.name == "engine.flush")
    assert flush.attrs["jobs"][0] == "j0#c0" and flush.attrs["requests"] == 3
    assert flush.attrs["queue_wait_ms"] >= 0
    passes = [r for r in ring if r.name == "rm.pass"]
    assert sum(r.attrs["started"] for r in passes) == len(handles)


def test_fetch_counts_bound_the_batched_loop():
    t0 = time.perf_counter()
    handles = _replay()
    ring = telemetry.spans(t0, time.perf_counter())
    packs = [r for r in ring if r.name == "engine.pack"]
    fetches = [r for r in ring if r.name == "engine.fetch"]
    levels = SA.num_exchanges * SA.iters_per_exchange
    bound = levels * (min(SA.max_success, SA.max_neighbors)
                      + SA.max_neighbors)
    assert len(packs) == len(fetches) > 0
    for pack, fetch in zip(packs, fetches):
        a, c = pack.attrs, fetch.attrs
        assert a["kernel_order"] == a["bucket"] == 16      # reference path
        assert a["rows"] <= a["padded_rows"] and a["orders"] <= 16 * a["rows"]
        assert c["lanes"] == a["padded_rows"] * 2 * SA.solvers
        assert levels <= c["rounds_executed"] <= bound
        assert c["rounds_executed"] <= c["lane_rounds"] <= (
            c["lanes"] * c["rounds_executed"])
        assert 0 <= c["accepts"] <= c["lanes"] * levels * SA.max_success
    assert all(h.response.seconds > 0 for h in handles
               if not h.response.cached)


def test_bucket_dispatch_names_the_delta_form(monkeypatch):
    """Every bucket ``engine.dispatch`` span carries the dense delta form
    ``kernel_ops.delta_form`` picks for its bucket: the reference off TPU,
    and on TPU the row form at the engine's buckets."""
    from repro.kernels import ops
    t0 = time.perf_counter()
    _replay()
    ring = telemetry.spans(t0, time.perf_counter(), ["engine.dispatch"])
    buckets = [r for r in ring if r.attrs.get("path") == "bucket"]
    assert buckets and all(r.attrs["delta_form"] == "reference"
                           for r in buckets)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert {ops.delta_form(b) for b in MappingEngine().buckets} == {"row"}


def test_response_seconds_are_the_groups_dispatch_and_fetch():
    from _fixtures import instance
    from repro.serve import MapRequest
    eng = MappingEngine(buckets=(16,), polish_rounds=4, sa_cfg=SA)
    for i in range(3):
        C, M = instance(10, 60 + i)
        eng.submit(MapRequest(job_id=f"r{i}", C=C, M=M, seed=i))
    t0 = time.perf_counter()
    out = eng.flush()
    ring = telemetry.spans(t0, time.perf_counter())
    d, f = (next(r for r in ring if r.name == name)
            for name in ("engine.dispatch", "engine.fetch"))
    assert d.attrs == {"algorithm": "psa", "tier": "default",
                       "path": "bucket", "delta_form": "reference"}
    assert {r.seconds for r in out.values()} == {(0.0 + d.dur + f.dur) / 3}
