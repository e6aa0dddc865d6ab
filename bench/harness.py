"""Drive one benchmark cell through the program's served entry.

The program under test is ``ResourceManager.step()`` on a 4096-node
``ClusterState`` with a ``MappingEngine`` behind it.  The resource manager
is a single synchronous caller on a virtual clock: every job start carves
K candidate node sets, flushes their mapping wave and waits for it, so the
harness drives it in a closed loop and the wall time of that loop is what
a user of the machine waits for.

Set-up (untimed by the window, timed as ``setup_s``):

1. the cell's own programs are executed once: every dense bucket wave and
   polish the stream can dispatch (``MappingEngine.warmup(execute=True)``);
2. the stream runs from an empty machine with the mix's backlog always
   queued, placed by an identity stand-in for the engine, until the
   machine is at least ``min_occupancy`` full and ``fill_turnover`` times
   as many jobs have completed as are running; then the real engine
   places ``prime_placements`` jobs: the state of a machine, and of an
   engine, that have been in service.

The window then drives ``step()`` for the given seconds.  The harness
stamps each commit through a ``ClusterState`` subclass (``promote``) and
times candidate carving there too; ``check`` holds the commits, the
permutations and the objectives to ``reference`` once the window closed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import reference
import traffic
from trace_reduce import WINDOW_SPAN

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = CHECKOUT / "BENCHMARK.json",
              bench: Path = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration and
    mix (``<bench>/mixes/<traffic>.json``) read from their files and its
    metrics selected."""
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((Path(spec_path).parent / configs[w["config"]]["file"])
                        .read_text())
    mix = json.loads((bench / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def machine(config: dict) -> np.ndarray:
    m = config["machine"]
    if m["kind"] != "grid3d":
        raise ValueError(f"unknown machine kind {m['kind']!r}")
    return reference.grid_distances(m["dims"])


class CompileMeter:
    """Backend compiles (persistent-cache loads included, as JAX reports
    them under the same event) and traces, from JAX's monitoring events,
    on the thread that made the meter: the one that drives the resource
    manager and its synchronous engine."""

    def __init__(self):
        import jax
        self.thread = threading.get_ident()
        self.compiles: List[tuple] = []     # (perf_counter, fun_name)
        self.traces: List[float] = []       # perf_counter of each trace
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if threading.get_ident() != self.thread:
            return
        if event == _BACKEND_COMPILE:
            self.compiles.append((time.perf_counter(), kw.get("fun_name", "?")))
        elif event == _TRACE:
            self.traces.append(time.perf_counter())
        elif event == _CACHE_HIT:
            self.cache_loads += 1

    def between(self, t0: float, t1: float) -> tuple:
        """(programs compiled or loaded, jaxprs traced) in [t0, t1]."""
        return ([name for at, name in self.compiles if t0 <= at <= t1],
                sum(t0 <= at <= t1 for at in self.traces))


class Recorder:
    """The harness's spans and stamps: carve time per call, commit stamps,
    and ``TraceAnnotation`` spans when the run is traced."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.in_window = False
        self.pass_start = 0.0
        self.last_carve_s = 0.0
        self.commits: List[reference.Commit] = []
        self.latency_s: Dict[str, float] = {}
        self.carve_s: Dict[str, float] = {}
        self.rm = None
        self.jobs: Dict[str, traffic.Job] = {}

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def on_commit(self, job_id: str, nodes: np.ndarray, t: float) -> None:
        job = self.jobs[job_id]
        self.commits.append(reference.Commit(
            job_id=job_id, size=job.size, run_s=job.run_s,
            clock=self.rm.clock, nodes=np.array(nodes, copy=True),
            in_window=self.in_window))
        if self.in_window:
            self.latency_s[job_id] = t - self.pass_start
            self.carve_s[job_id] = self.last_carve_s


def _harness_classes():
    from repro.serve import ClusterState, MappingEngine

    class HarnessCluster(ClusterState):
        """The program's cluster, with the harness's carve timer and commit
        stamp around the calls the resource manager makes."""

        def __init__(self, M, rec: Recorder):
            super().__init__(M)
            self.rec = rec

        def candidate_subsets(self, size, k=3, policies=("compact", "slab",
                                                        "scatter")):
            t0 = time.perf_counter()
            with self.rec.span("rm.carve"):
                out = super().candidate_subsets(size, k=k, policies=policies)
            self.rec.last_carve_s = time.perf_counter() - t0
            return out

        def promote(self, tag, job_id, nodes):
            with self.rec.span("rm.commit"):
                alloc = super().promote(tag, job_id, nodes)
            self.rec.on_commit(job_id, alloc.nodes, time.perf_counter())
            return alloc

    class HarnessEngine(MappingEngine):
        """The program's engine, with a span around each wave."""

        rec: Optional[Recorder] = None

        def flush(self):
            with self.rec.span("engine.wave"):
                return super().flush()

    return HarnessCluster, HarnessEngine


def wave_sizes(candidates: int) -> tuple:
    """Instance-axis sizes a wave of up to ``candidates`` can take: the
    engine pads waves to powers of two."""
    top = 1 << (candidates - 1).bit_length()
    return tuple(1 << i for i in range(top.bit_length()))


def warm(config: dict, stream: traffic.Stream) -> int:
    """Execute every program the cell's stream can dispatch, once.
    Returns the number of programs the engine warmed by shape."""
    from repro.serve import MappingEngine
    eng = MappingEngine(**config["engine"])
    rmc = config["rm"]
    algorithm, tier = eng.policy.resolve(rmc["algorithm"], rmc["deadline_ms"])
    sizes = [int(s) for s in np.unique(stream.sizes)]
    buckets = sorted({eng.bucket_for(s) for s in sizes} - {None})
    return eng.warmup(buckets=buckets, algorithms=(algorithm,), tiers=(tier,),
                      batch_sizes=wave_sizes(rmc["candidates"]),
                      warm_starts=(False, True), execute=True)


@dataclasses.dataclass
class Window:
    """Everything one run's window produced, for the metric readers."""
    seed: int
    t_open: float                   # perf_counter when the window opened
    window_s: float
    latency_s: List[float]          # per committed job, pass start to commit
    carve_s: List[float]            # per committed job, its carving call
    wave_s: List[float]             # per committed job, JobHandle.map_wall_s
    compiles: List[str]             # programs compiled or loaded in the window
    traces: int                     # jaxprs traced in the window
    degraded: int
    occupancy_open: float
    queued_open: int
    running_open: int
    fill_placements: int            # placements by the identity stand-in
    fill_s: float                   # host seconds of the fill and the priming
    engine_stats: Dict[str, int]    # EngineStats counters over the window
    commits: List[reference.Commit] = dataclasses.field(default_factory=list)
    answers: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    flows: Optional[Callable] = None    # job id -> the job's C
    setup_s: float = 0.0
    trace: Optional[dict] = None    # trace_reduce.summarize(), traced runs only
    checked: Optional[reference.Checked] = None     # set by check()

    @property
    def placements(self) -> int:
        return len(self.latency_s)


class IdentityEngine:
    """Set-up's stand-in for the engine while the stream fills the machine:
    every candidate is answered at once with the identity mapping and its
    F, computed on the host.  It shapes occupancy and fragmentation as the
    resource manager's own passes do, without a solve; the real engine
    takes over for the priming placements and the window."""

    running = False

    def __init__(self, max_batch: int):
        from repro.serve import MapResponse
        self.MapResponse = MapResponse
        self.max_batch = max_batch
        self.stats = types.SimpleNamespace(solver_batches=0)

    def submit(self, req) -> "_Answered":
        n = req.C.shape[0]
        f = float((np.asarray(req.C, np.float64)
                   * np.asarray(req.M, np.float64)).sum())
        return _Answered(self.MapResponse(
            job_id=req.job_id, perm=np.arange(n, dtype=np.int32),
            objective=f, baseline=f, algorithm="identity", n=n, bucket=None,
            cached=False, seconds=0.0))

    def flush(self) -> dict:
        return {}


class _Answered:
    """A future that is answered when it is made."""

    def __init__(self, response):
        self.response = response

    def result(self, timeout=None):
        return self.response


class Feeder:
    """Keeps the mix's backlog of jobs waiting in the resource manager."""

    def __init__(self, rm, stream: traffic.Stream, rec: Recorder, backlog: int):
        from repro.serve import JobSpec
        self.JobSpec = JobSpec
        self.rm, self.stream, self.rec = rm, stream, rec
        self.backlog = backlog
        self.handles: Dict[str, object] = {}

    def waiting(self) -> int:
        return len(self.handles) - len(self.rec.commits)

    def top_up(self) -> None:
        while self.waiting() < self.backlog:
            job = next(self.stream)
            job_id = f"j{job.index}"
            self.rec.jobs[job_id] = job
            self.handles[job_id] = self.rm.submit_job(self.JobSpec(
                job_id=job_id, size=job.size, run_s=job.run_s,
                arrival_s=self.rm.clock, C=self.stream.flows(job),
                seed=job.seed))

    def step(self) -> None:
        self.top_up()
        if self.rm.step() is None:
            self.rm.schedule()


def _engine_counts(engine) -> Dict[str, int]:
    s = engine.stats
    return {"solver_batches": s.solver_batches, "solver_calls": s.solver_calls,
            "cache_hits": s.cache_hits, "warm_starts": s.warm_starts}


def run_window(config: dict, mix: dict, M: np.ndarray, seed: int,
               seconds: float, meter: CompileMeter, traced: bool = False,
               trace_start: Optional[Callable] = None,
               trace_stop: Optional[Callable] = None,
               trace_seconds: float = 0.0) -> Window:
    """Bring the machine to its steady state from the seed's stream, then
    drive the window.

    Set-up: the identity stand-in fills the machine until it is at least
    ``min_occupancy`` full and ``fill_turnover`` times as many jobs have
    completed as are running; then the real engine places
    ``prime_placements`` more jobs, so that its caches hold what a
    service in use holds.

    With ``trace_start``/``trace_stop`` the profiler records the window's
    last ``trace_seconds``: it starts that long before the window closes
    and stops once it has closed, so the harness's own timers and counters
    cover the whole window in a traced run too."""
    from repro.serve import ResourceManager
    HarnessCluster, HarnessEngine = _harness_classes()
    t_fill = time.perf_counter()
    stream = traffic.Stream(config, mix, seed)
    rec = Recorder(traced)
    cluster = HarnessCluster(M, rec)
    engine = HarnessEngine(**config["engine"])
    engine.rec = rec
    rmc = config["rm"]
    rm = ResourceManager(cluster, engine, candidates=rmc["candidates"],
                         policies=tuple(rmc["policies"]),
                         backfill=rmc["backfill"], algorithm=rmc["algorithm"],
                         deadline_ms=rmc["deadline_ms"])
    rec.rm = rm
    feeder = Feeder(rm, stream, rec, int(mix["backlog"]))

    rm.engine = IdentityEngine(engine.max_batch)
    feeder.top_up()
    rm.schedule()
    while True:
        running = len(rec.commits) - rm.stats.completed
        occupancy = 1.0 - cluster.num_free / cluster.num_nodes
        if (rm.stats.completed >= mix["fill_turnover"] * running
                and occupancy >= mix["min_occupancy"]):
            break
        if len(rec.commits) >= mix["max_fill_placements"]:
            raise RuntimeError(
                f"set-up did not reach the steady state in "
                f"{len(rec.commits)} placements (occupancy {occupancy})")
        feeder.step()
    fill = len(rec.commits)
    rm.engine = engine
    while len(rec.commits) < fill + mix["prime_placements"]:
        feeder.step()
    feeder.top_up()
    queued_open = feeder.waiting()
    running = len(rec.commits) - rm.stats.completed
    occupancy = 1.0 - cluster.num_free / cluster.num_nodes
    stats_open = _engine_counts(engine)

    t_open = time.perf_counter()
    deadline = t_open + seconds
    rec.in_window = True

    def drive(until: float) -> None:
        while time.perf_counter() < until:
            feeder.top_up()
            rec.pass_start = time.perf_counter()
            with rec.span("rm.step"):
                rm.step()

    if trace_start is None:
        drive(deadline)
    else:
        drive(deadline - trace_seconds)
        trace_start()
        with rec.span(WINDOW_SPAN):
            drive(deadline)
    t_close = time.perf_counter()
    rec.in_window = False
    if trace_stop is not None:
        trace_stop()

    window_jobs = [c.job_id for c in rec.commits if c.in_window]
    answers = {j: (feeder.handles[j].response.perm,
                   feeder.handles[j].response.objective) for j in window_jobs}

    def flows(job_id):
        return stream.flows(rec.jobs[job_id])

    compiles, traces = meter.between(t_open, t_close)
    stats_close = _engine_counts(engine)
    return Window(
        seed=seed, t_open=t_open, window_s=t_close - t_open,
        latency_s=[rec.latency_s[j] for j in window_jobs],
        carve_s=[rec.carve_s[j] for j in window_jobs],
        wave_s=[feeder.handles[j].map_wall_s for j in window_jobs],
        compiles=compiles, traces=traces,
        degraded=sum(bool(feeder.handles[j].response.degraded)
                     for j in window_jobs),
        occupancy_open=occupancy, queued_open=queued_open,
        running_open=running, fill_placements=fill, fill_s=t_open - t_fill,
        engine_stats={k: stats_close[k] - stats_open[k] for k in stats_open},
        commits=rec.commits, answers=answers, flows=flows)


def check(w: Window, M: np.ndarray, limits: dict) -> reference.Checked:
    """Hold every commit of the window to the reference, on the host, once
    the window has closed; the result is kept as ``w.checked``."""
    w.checked = reference.check(w.commits, w.answers, w.flows, M,
                                f_gap_limit=limits["f_gap"])
    return w.checked
