"""Transport seam: length-prefixed frames + process-isolated workers.

The frame layer is unit-tested against every way a pipe can lie (clean
EOF, truncated header, truncated payload, implausible length, undecodable
pickle).  The subprocess worker is then exercised end to end under *real*
faults -- the child SIGKILLs itself, corrupts its own stdout, or is
SIGSTOP'd into a zombie, all by deterministic count via FaultPlan -- and
every recovered result is checked bitwise against a single
``MappingEngine(warm_start=False)``.
"""
import io
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from repro.serve import EngineFleet, FaultPlan, MappingEngine, MapRequest
from repro.serve.transport import (_HEADER, FrameError, SubprocessWorker,
                                   read_frame, write_frame)

from _fixtures import SA_SMALL, instance as _instance

# Matches tests/test_fleet.py so child engines reuse the same compiled
# bucket programs via the shared persistent JAX cache.
ENGINE_KW = dict(buckets=(8,), sa_cfg=SA_SMALL, polish_rounds=0,
                 max_batch=4, num_processes=2, flush_deadline_ms=10.0)


def make_reqs(k, n=6, algorithm="psa", seed0=0):
    reqs = []
    for i in range(k):
        C, M = _instance(n, seed0 + i)
        reqs.append(MapRequest(job_id=f"j{i}", C=C, M=M,
                               algorithm=algorithm, seed=seed0 + i))
    return reqs


def single_engine_results(reqs):
    eng = MappingEngine(warm_start=False, **ENGINE_KW)
    futs = [eng.submit(r) for r in reqs]
    eng.flush()
    return {r.job_id: f.result(timeout=0) for r, f in zip(reqs, futs)}


def assert_bitwise_equal(resps, refs):
    assert set(resps) == set(refs)
    for job_id, resp in resps.items():
        ref = refs[job_id]
        np.testing.assert_array_equal(resp.perm, ref.perm)
        assert resp.objective == ref.objective
        assert (resp.algorithm, resp.tier) == (ref.algorithm, ref.tier)


@contextmanager
def make_fleet(**kw):
    fleet = EngineFleet(transport="subprocess", **{**ENGINE_KW, **kw})
    try:
        yield fleet
    finally:
        if not fleet._shutdown:
            fleet.stop()


def wait_until(pred, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


# ---------------------------------------------------------------- frames
def test_frame_round_trip_is_lossless():
    buf = io.BytesIO()
    C, M = _instance(6, seed=0)
    obj = ("result", 17, {"perm": np.arange(6), "C": C, "M": M,
                          "note": "payload"})
    write_frame(buf, obj)
    write_frame(buf, ("beat",))
    buf.seek(0)
    back = read_frame(buf)
    assert back[0] == "result" and back[1] == 17
    np.testing.assert_array_equal(back[2]["perm"], np.arange(6))
    assert back[2]["C"].tobytes() == C.tobytes()      # bit-for-bit
    assert back[2]["M"].tobytes() == M.tobytes()
    assert read_frame(buf) == ("beat",)
    with pytest.raises(EOFError):
        read_frame(buf)                               # clean close


def test_frame_writer_lock_serializes_concurrent_writers():
    buf = io.BytesIO()
    lock = threading.Lock()
    threads = [threading.Thread(target=write_frame,
                                args=(buf, ("beat", i), lock))
               for i in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    buf.seek(0)
    seen = sorted(read_frame(buf)[1] for _ in range(8))
    assert seen == list(range(8))
    with pytest.raises(EOFError):
        read_frame(buf)


def test_truncated_header_is_frame_error_not_eof():
    # a worker that died mid-write looks corrupt, not cleanly closed
    with pytest.raises(FrameError, match="header"):
        read_frame(io.BytesIO(b"\x00\x00"))


def test_truncated_payload_is_frame_error():
    buf = io.BytesIO(_HEADER.pack(100) + b"short")
    with pytest.raises(FrameError, match="payload"):
        read_frame(buf)


def test_implausible_length_is_frame_error():
    # 0xdeadbeef as a length -- exactly what FaultPlan's stdout
    # corruption injects -- must be rejected before any giant read
    with pytest.raises(FrameError, match="implausible"):
        read_frame(io.BytesIO(b"\xde\xad\xbe\xef" * 16))


def test_undecodable_payload_is_frame_error():
    payload = b"not a pickle, definitely"
    buf = io.BytesIO(_HEADER.pack(len(payload)) + payload)
    with pytest.raises(FrameError, match="undecodable"):
        read_frame(buf)


# ------------------------------------------------------- construction rules
def test_subprocess_fleet_rejects_unpicklable_configs():
    with pytest.raises(ValueError, match="process boundary"):
        EngineFleet(workers=1, transport="subprocess",
                    engine_factory=lambda: None, **ENGINE_KW)
    with pytest.raises(ValueError, match="transport"):
        EngineFleet(workers=1, transport="carrier-pigeon", **ENGINE_KW)


# ----------------------------------------------------------- e2e: parity
def test_subprocess_fleet_matches_plain_engine_bitwise():
    reqs = make_reqs(5)
    refs = single_engine_results(reqs)
    with make_fleet(workers=1) as fleet:
        futs = [fleet.submit(r) for r in reqs]
        out = fleet.flush()
        assert all(f.done() for f in futs)
    assert_bitwise_equal(out, refs)
    assert fleet.stats.worker_deaths == 0
    assert isinstance(fleet.workers[0], SubprocessWorker)


# --------------------------------------------------------- e2e: real faults
def test_sigkill_mid_wave_respawns_and_stays_bitwise():
    """The only worker SIGKILLs itself after one delivery: the
    coordinator sees EOF on the pipe, respawns a fresh process, and the
    requeued remainder still matches the single engine bitwise."""
    reqs = make_reqs(4, seed0=40)
    refs = single_engine_results(reqs)
    with make_fleet(workers=1,
                    fault_plan=FaultPlan(sigkill_worker_at={0: 1})) as fleet:
        futs = [fleet.submit(r) for r in reqs]
        out = fleet.flush()
        assert all(f.done() for f in futs)
    assert_bitwise_equal(out, refs)
    assert fleet.stats.worker_deaths == 1
    assert fleet.stats.respawns >= 1
    assert fleet.stats.requeued == 3       # the undelivered wave remainder
    assert fleet.stats.failed == 0
    assert fleet.stats.first_recovery_s is not None
    assert fleet.stats.first_recovery_s > 0.0


def test_corrupt_stdout_declares_worker_dead_and_recovers():
    """The child spews 0xdeadbeef into its result pipe: FrameError (a
    pickle stream cannot resync), worker declared dead, wave requeued."""
    reqs = make_reqs(3, seed0=60)
    refs = single_engine_results(reqs)
    with make_fleet(workers=1,
                    fault_plan=FaultPlan(corrupt_stdout_at={0: 1})) as fleet:
        [fleet.submit(r) for r in reqs]
        out = fleet.flush()
    assert_bitwise_equal(out, refs)
    assert fleet.stats.worker_deaths == 1
    assert fleet.stats.requeued == 2
    assert fleet.stats.failed == 0


@pytest.mark.slow
def test_sigstop_zombie_caught_by_staleness_detector():
    """A SIGSTOP'd child is the nastiest failure: the process is alive
    (no EOF) but both its solve and its heartbeat thread are frozen.
    Only the coordinator's staleness detector can catch it."""
    reqs = make_reqs(8, seed0=80)
    refs = single_engine_results(reqs)
    with make_fleet(workers=2, heartbeat_timeout_s=2.0,
                    fault_plan=FaultPlan(sigstop_worker_at={0: 1})) as fleet:
        futs = [fleet.submit(r) for r in reqs]
        out = fleet.flush()
        assert all(f.done() for f in futs)
        assert fleet.stats.worker_deaths == 1
        assert fleet.stats.requeued >= 1
        assert fleet.stats.failed == 0
    # stop() must reap the stopped process (SIGCONT + SIGKILL), not hang
    assert all(not w.alive for w in fleet.workers)
    assert all(w._proc is None or w._proc.poll() is not None
               for w in fleet.workers)
    assert_bitwise_equal(out, refs)


@pytest.mark.slow
def test_subprocess_fleet_shards_across_workers_bitwise():
    reqs = make_reqs(9, seed0=20)
    refs = single_engine_results(reqs)
    with make_fleet(workers=3) as fleet:
        [fleet.submit(r) for r in reqs]
        out = fleet.flush()
    assert_bitwise_equal(out, refs)
    assert fleet.stats.dispatched_waves == 3
    assert fleet.stats.worker_deaths == 0


@pytest.mark.slow
def test_per_worker_cache_dir_created_and_used(tmp_path):
    reqs = make_reqs(2, seed0=200)
    refs = single_engine_results(reqs)
    with make_fleet(workers=1, worker_cache_dir=str(tmp_path)) as fleet:
        [fleet.submit(r) for r in reqs]
        out = fleet.flush()
    assert_bitwise_equal(out, refs)
    # the child populated its private compilation cache
    w0 = tmp_path / "w0"
    assert w0.is_dir() and any(w0.iterdir())


def test_subprocess_warmup_runs_in_a_child():
    """The coordinator's warmup compiles in a worker process (the
    coordinator stays off the devices) and returns the child's count."""
    with make_fleet(workers=1) as fleet:
        # one psa program at bucket 8, wave 1 (polish_rounds=0: no polish)
        assert fleet.warmup(batch_sizes=(1,), warm_starts=(False,)) == 1
        reqs = make_reqs(2, seed0=300)
        [fleet.submit(r) for r in reqs]
        assert_bitwise_equal(fleet.flush(), single_engine_results(reqs))


def test_child_that_cannot_start_jax_fails_requests_fast(monkeypatch):
    """A worker whose JAX cannot start (here: an unknown platform; on a
    chip host, a device another process holds) reports it once, and
    every request fails with that error instead of waiting out the
    heartbeat and compile grace."""
    monkeypatch.setenv("JAX_PLATFORMS", "nosuch")
    with make_fleet(workers=1, compiling_grace_s=600.0) as fleet:
        t0 = time.monotonic()
        fut = fleet.submit(make_reqs(1, seed0=400)[0])
        if not fleet.running:
            fleet.start()
        exc = fut.exception(timeout=60)
        assert time.monotonic() - t0 < 60
        assert isinstance(exc, RuntimeError)
        assert "could not start JAX" in str(exc) and "nosuch" in str(exc)
        with pytest.raises(RuntimeError, match="could not start JAX"):
            fleet.warmup()


def test_subprocess_fleet_refused_when_coordinator_holds_accelerator(
        monkeypatch):
    """A chip belongs to one process: once this process has initialized
    JAX on an accelerator, a subprocess fleet (whose children need the
    devices) is refused up front with a message naming the platform."""
    import jax
    from jax._src import xla_bridge
    jax.devices()                                  # backends initialized
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already initialized JAX on tpu"):
        EngineFleet(transport="subprocess", workers=1, **ENGINE_KW)
    # the thread transport shares this process's devices: still allowed
    EngineFleet(transport="thread", workers=1, **ENGINE_KW).stop()
