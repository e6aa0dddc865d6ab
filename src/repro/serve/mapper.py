"""Async, deadline-aware mapping service: the resource-manager-facing engine.

The paper's premise is that mapping requests arrive as a *stream* while
resources are being scheduled, so the solver must answer within the
resource manager's timeout.  The engine is built around that contract:

  1. :meth:`MappingEngine.submit` is non-blocking and returns a
     :class:`MapFuture`; the caller (a scheduler allocation loop) keeps
     admitting jobs while solves are in flight and collects each mapping
     with ``future.result()``.
  2. A background *flusher* thread (``start()`` / ``stop()``) dispatches a
     (bucket, algorithm, budget-tier) group as soon as it fills
     (``max_batch``) or when the oldest queued request is about to exceed
     ``flush_deadline_ms`` -- so latency is bounded without giving up
     batching.  ``flush()`` remains available for synchronous use and is
     bitwise-equivalent: the flusher runs the very same code path on the
     same drained queue.
  3. A :class:`DeadlinePolicy` picks algorithm + solver budget per request
     (paper S5: SA meets tight resource-manager timeouts, the composite
     algorithm buys accuracy when there is slack): requests may carry a
     ``deadline_ms`` and/or ``algorithm="auto"``.
  4. Each instance is padded to the smallest size *bucket* (default
     32/64/128) and whole groups dispatch through the batched entry points
     ``annealing.run_psa_batch`` / ``genetic.run_pga_batch`` /
     ``composite.run_pca_batch`` -- one accelerator program solves B
     instances at once.
  5. A two-tier store serves repeats: the *exact* tier is an LRU keyed by
     the full instance digest (same instance => cached permutation, no
     solve); the *shape* tier remembers the latest solution per
     (order, system-graph) digest, and a near-miss -- same nodes and
     topology, different flows -- warm-starts the new solve by seeding the
     solver chains with the cached permutation (``init_perm``), which the
     solvers guarantee never ends worse than the seed.

  6. With a device ``mesh`` the engine shards each wave's instance axis
     across ``mesh.shape[instance_axis]`` devices
     (``core.batch_sharded.run_*_batch_sharded``): the wave is padded to a
     multiple of the axis size, every device solves its local slice, and
     results stay bitwise-equal to the single-device path -- batching
     becomes real hardware parallelism instead of just dispatch
     efficiency.

  7. :meth:`MappingEngine.warmup` AOT-precompiles every bucket program
     (``jit(...).lower().compile()``) at service start, so the first wave
     of each shape pays a persistent-cache reload instead of a full XLA
     compile (``benchmarks/scheduler_sim.py --warmup`` measures the
     warm-vs-cold p99 difference).

  8. Orders above every dense bucket route by *large bucket*
     (512/1024/4096 by default) to the sparse + multilevel pipeline
     (``core.multilevel``) once they reach ``multilevel_min_n`` — the
     dense O(n²) ceiling stops applying, and each level runs at the
     next power of two of its order (docs/DESIGN.md §10); smaller
     oversize orders keep the unpadded exact-size path.

Queue, cache, and stats are thread-safe; solves are serialized by a
dispatch lock so the flusher and synchronous callers can coexist.

Resource-manager integration (the paper's deployment loop; see
``benchmarks/scheduler_sim.py`` for the full allocate -> map -> run ->
release version)::

    from repro.serve.cluster import ClusterState
    from repro.serve.mapper import MapRequest, MappingEngine

    cluster = ClusterState(M_system)          # machine distance matrix
    with MappingEngine() as engine:           # starts the flusher thread
        for job in scheduler_stream:
            alloc = cluster.allocate(job.job_id, job.size)
            fut = engine.submit(MapRequest(
                job_id=job.job_id, C=job.traffic, M=alloc.M_sub,
                algorithm="auto", deadline_ms=job.deadline_ms))
            # ... keep admitting jobs; later:
            resp = fut.result()               # process k -> local slot
            nodes = alloc.physical(resp.perm)  # -> physical node ids
            launch(job, nodes); cluster.release(job.job_id)

Padding is exact, not approximate: flows touching padded slots are zeroed
and the batched solvers keep real processes on real nodes (see
``qap.masked_random_permutation``), so a padded solve returns the same
objective the unpadded instance would -- verified bitwise against the
per-instance runners in ``tests/test_mapper.py``.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (annealing, batch_sharded, composite, genetic,
                        mapping as mapping_lib, multilevel)
from repro.kernels import ops as kernel_ops
from repro.serve import telemetry

DEFAULT_BUCKETS = (32, 64, 128)

# Routing labels for the sparse/multilevel path: orders above the dense
# buckets (and >= multilevel_min_n) group under the smallest large bucket
# that holds them and solve via core.multilevel, each level at the power
# of two of its order — the dense O(n²) solvers never see these instances
# (docs/DESIGN.md §10).
LARGE_BUCKETS = (512, 1024, 4096)

ALGORITHMS = ("psa", "pga", "pca")
AUTO = "auto"                       # algorithm chosen by the deadline policy

TIERS = ("default", "tight")


class QueueFull(RuntimeError):
    """Admission control: the engine/fleet/RM queue is at ``max_pending``.

    :meth:`MappingEngine.submit` (and the fleet's) never raise it -- they
    return an already-failed future carrying it, so streaming callers keep
    one code path -- while :meth:`~repro.serve.rm.ResourceManager.submit_job`
    raises it directly (a rejected job must not get a handle)."""


class MapCancelled(RuntimeError):
    """Raised by :meth:`MapFuture.result` after :meth:`MapFuture.cancel`.

    Deliberately *not* ``concurrent.futures.CancelledError`` (a
    ``BaseException`` since 3.8): engine/fleet internals and callers
    uniformly handle ``Exception``."""


@dataclass(frozen=True, kw_only=True)
class MapRequest:
    """One job's mapping problem: program graph C, system graph M.

    Stability contract: part of the public ``repro.serve`` API.  Fields
    are keyword-only and frozen; new fields are appended with defaults,
    existing fields are never renamed, retyped, or reordered within a
    major version.  Construct with keywords only.

    ``cache_seed=True`` folds the seed into the cache digest: the same
    instance with a different seed then gets a fresh, independent solve
    (best-of-k restart sweeps) instead of the shape-level cached one --
    and near-miss warm starts are skipped so restarts stay independent.

    ``deadline_ms`` is the resource manager's answer budget for this
    request; with ``algorithm="auto"`` the engine's
    :class:`DeadlinePolicy` picks algorithm and solver budget from it.
    """
    job_id: str
    C: np.ndarray              # (n, n) flow matrix
    M: np.ndarray              # (n, n) distance matrix
    algorithm: str = "psa"
    seed: int = 0
    cache_seed: bool = False
    deadline_ms: Optional[float] = None


@dataclass(frozen=True, kw_only=True)
class MapResponse:
    """One solved mapping.  Same stability contract as
    :class:`MapRequest`: keyword-only, frozen, append-only fields."""
    job_id: str
    perm: np.ndarray           # (n,) process -> node
    objective: float           # F(perm)
    baseline: float            # F(identity)
    algorithm: str             # resolved algorithm (policy applied)
    n: int
    bucket: Optional[int]      # padded size (None = solved at exact size)
    cached: bool
    seconds: float             # the group's engine.dispatch + engine.fetch
    #                            wall time / batch_size
    batch_size: int = 1        # requests served by the dispatch (0 = cached)
    tier: str = "default"      # solver budget tier the policy picked
    warm_start: bool = False   # solve was seeded from a near-miss cache hit
    degraded: bool = False     # deadline fallback, not a real solve
    degrade_reason: str = ""   # "deadline_shape_cache" | "deadline_identity"

    @property
    def improvement(self) -> float:
        if self.baseline == 0:
            return 0.0
        return (self.baseline - self.objective) / self.baseline


class MapFuture:
    """Handle for one submitted request; resolved by a flush (either the
    background flusher thread or an explicit :meth:`MappingEngine.flush`).

    A scheduler loop typically keeps admitting jobs and polls ``done()``,
    collecting each finished mapping with ``result(timeout)`` (which
    re-raises the solve's exception, if any; ``exception()`` inspects it
    without raising).  ``resolved_at`` is the ``time.monotonic()`` stamp of
    resolution, so submit-to-resolve latency is
    ``future.resolved_at - t_submit`` — this is what
    ``benchmarks/scheduler_sim.py`` reports as mapping latency.

    Resolution is *claimed* under a per-future lock: exactly one of
    ``_resolve`` / ``_fail`` / :meth:`cancel` wins, the others are no-ops
    returning False.  A caller that gives up on a future (e.g. its own
    ``result(timeout)`` expired) should :meth:`cancel` it -- otherwise the
    request stays in flight forever with nobody to collect it.  The engine
    and fleet skip cancelled requests at dispatch when they can and count
    every cancelled resolution in ``stats.cancelled``.
    """

    __slots__ = ("_event", "_response", "_exception", "resolved_at",
                 "_claim", "_cancelled")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._response: Optional[MapResponse] = None
        self._exception: Optional[BaseException] = None
        self.resolved_at: Optional[float] = None   # time.monotonic() stamp
        self._claim = threading.Lock()             # resolution claim
        self._cancelled = False

    def done(self) -> bool:
        return self._event.is_set()

    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self) -> bool:
        """Abandon the request: the future resolves with
        :class:`MapCancelled` and any late real result is discarded by
        the claim guard.  Returns False when already resolved (cancel
        lost the race -- the result stands and remains readable)."""
        return self._fail(MapCancelled("mapping request cancelled by caller"),
                          cancelled=True)

    def result(self, timeout: Optional[float] = None) -> MapResponse:
        if not self._event.wait(timeout):
            raise TimeoutError("mapping future not resolved within timeout")
        if self._exception is not None:
            raise self._exception
        assert self._response is not None
        return self._response

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            raise TimeoutError("mapping future not resolved within timeout")
        return self._exception

    def _resolve(self, response: MapResponse) -> bool:
        with self._claim:
            if self._event.is_set():
                return False
            self._response = response
            self.resolved_at = time.monotonic()
            self._event.set()
            return True

    def _fail(self, exc: BaseException, cancelled: bool = False) -> bool:
        with self._claim:
            if self._event.is_set():
                return False
            self._exception = exc
            self._cancelled = cancelled
            self.resolved_at = time.monotonic()
            self._event.set()
            return True


@dataclass(frozen=True)
class DeadlinePolicy:
    """Deadline -> (algorithm, solver-budget tier), after paper S5.

    Under tight timeouts only SA answers in time at useful quality, so
    ``deadline_ms <= tight_ms`` maps to PSA on the reduced "tight" budget;
    with real slack (``deadline_ms >= slack_ms``) the composite algorithm
    is worth its extra cost; in between, PSA on the default budget.
    An explicit (non-"auto") algorithm is honored -- the deadline then
    only selects the budget tier.
    """
    tight_ms: float = 200.0
    slack_ms: float = 2000.0

    def resolve(self, algorithm: str,
                deadline_ms: Optional[float]) -> Tuple[str, str]:
        tier = "tight" if (deadline_ms is not None
                           and deadline_ms <= self.tight_ms) else "default"
        if algorithm != AUTO:
            return algorithm, tier
        if deadline_ms is None:
            return "psa", "default"
        if tier == "tight":
            return "psa", "tight"
        if deadline_ms >= self.slack_ms:
            return "pca", "default"
        return "psa", "default"


@dataclass
class EngineStats:
    submitted: int = 0
    cache_hits: int = 0
    warm_starts: int = 0       # solves seeded from a shape-tier near miss
    solver_batches: int = 0    # batched dispatches issued
    solver_calls: int = 0      # instances that went through a solver
    full_bucket_flushes: int = 0   # flusher waves triggered by a full group
    deadline_flushes: int = 0      # flusher waves triggered by the deadline
    warmup_programs: int = 0       # programs precompiled by warmup()
    cancelled: int = 0             # futures cancelled by their callers
    rejected: int = 0              # submits refused by max_pending


@dataclass
class _Pending:
    """A queued request plus everything the flusher needs to serve it."""
    req: MapRequest
    future: MapFuture
    algorithm: str             # resolved by the deadline policy
    tier: str
    t_submit: float            # time.monotonic()


def validate_request(req: MapRequest) -> None:
    """Reject malformed requests in the caller's thread (shared by
    :meth:`MappingEngine.submit` and the fleet coordinator): a digest or
    cast error inside a flusher/worker thread would otherwise surface
    nowhere."""
    if req.algorithm not in ALGORITHMS + (AUTO,):
        raise ValueError(
            f"algorithm must be one of {ALGORITHMS + (AUTO,)}")
    if req.C.shape != req.M.shape or req.C.shape[0] != req.C.shape[1]:
        raise ValueError("C and M must be square and same order")
    for name, a in (("C", req.C), ("M", req.M)):
        if not np.issubdtype(np.asarray(a).dtype, np.number) or \
                np.iscomplexobj(a):
            raise ValueError(f"{name} must be a real numeric matrix")


def _keeps_counts(cfg: annealing.SAConfig, bucket: int) -> bool:
    """Does a bucket PSA solve keep the event loop's counts?  Only the
    event loop has them."""
    return annealing.resolved_loop(cfg, bucket) == "event"


def _tighten_sa(cfg: annealing.SAConfig) -> annealing.SAConfig:
    """Reduced-budget SA for the tight deadline tier (~1/4 the work)."""
    return replace(cfg,
                   num_exchanges=max(1, cfg.num_exchanges // 2),
                   solvers=max(1, cfg.solvers // 2))


def _tighten_ga(cfg: genetic.GAConfig) -> genetic.GAConfig:
    return replace(cfg, generations=max(1, cfg.generations // 2))


class MappingEngine:
    """submit -> future; queue -> bucket -> batched solve -> two-tier cache.

    One engine instance is meant to live for the whole scheduler process;
    compiled programs are reused across flushes because bucket shapes and
    configs are stable.  Call :meth:`start` to run the background flusher
    (or use the engine as a context manager); without it the engine
    behaves synchronously via :meth:`flush`.

    With ``mesh`` (a ``jax.sharding.Mesh`` holding an ``instance_axis``
    axis, e.g. from ``launch.mesh.make_instance_mesh``) every bucket wave
    is dispatched with its instance axis sharded across the mesh devices
    (``core.batch_sharded``) — bitwise-identical results, one wave solved
    by N devices instead of one.
    """

    def __init__(self, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 cache_size: int = 256, num_processes: int = 2,
                 sa_cfg: Optional[annealing.SAConfig] = None,
                 ga_cfg: Optional[genetic.GAConfig] = None,
                 polish_rounds: int = 200,
                 flush_deadline_ms: float = 20.0,
                 max_batch: int = 32,
                 policy: Optional[DeadlinePolicy] = None,
                 warm_start: bool = True,
                 pad_batches: bool = True,
                 mesh=None,
                 instance_axis: str = batch_sharded.DEFAULT_AXIS,
                 large_buckets: Sequence[int] = LARGE_BUCKETS,
                 multilevel_min_n: int = 256,
                 multilevel_cfg: Optional[multilevel.MultilevelConfig
                                          | Mapping] = None,
                 max_pending: Optional[int] = None):
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets:
            raise ValueError("need at least one size bucket")
        # Large buckets are routing labels, not padded sizes: an order
        # above every dense bucket (and >= multilevel_min_n) groups under
        # its large bucket and solves through core.multilevel (which pads
        # each level itself).  Orders below the threshold keep the unpadded
        # exact-size path (bucket None).  A value also present in the
        # dense buckets stays dense — bucket_for() wins.
        self.large_buckets = tuple(sorted(int(b) for b in large_buckets))
        self._large_set = frozenset(self.large_buckets) - frozenset(self.buckets)
        self.multilevel_min_n = int(multilevel_min_n)
        # a configuration file gives the multilevel settings as a mapping
        # of MultilevelConfig's fields
        if isinstance(multilevel_cfg, Mapping):
            multilevel_cfg = multilevel.MultilevelConfig(**multilevel_cfg)
        self.multilevel_cfg = multilevel_cfg or multilevel.MultilevelConfig()
        self.cache_size = int(cache_size)
        self.num_processes = int(num_processes)
        self.polish_rounds = int(polish_rounds)
        self.flush_deadline_ms = float(flush_deadline_ms)
        self.max_batch = int(max_batch)
        self.policy = policy or DeadlinePolicy()
        # Admission control: queued-but-undispatched requests beyond this
        # are rejected (submit returns an already-failed QueueFull future).
        # None = unbounded, the pre-backpressure behavior.
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None)")
        self.max_pending = max_pending
        self.warm_start = bool(warm_start)
        self.pad_batches = bool(pad_batches)
        # mesh: a jax.sharding.Mesh (or None).  Bucket waves then dispatch
        # through core.batch_sharded, the instance axis sharded over
        # mesh.shape[instance_axis]; results are bitwise-equal to the
        # unsharded path, so the cache digest does not include the mesh.
        if mesh is not None and instance_axis not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {instance_axis!r}; "
                f"axes: {tuple(mesh.shape)}")
        self.mesh = mesh
        self.instance_axis = instance_axis
        self.sa_cfg = sa_cfg or annealing.SAConfig(
            max_neighbors=25, iters_per_exchange=30, num_exchanges=20,
            solvers=8)
        self.ga_cfg = ga_cfg or genetic.GAConfig(generations=80, pop_size=32)
        self._tier_cfgs = {
            "default": (self.sa_cfg, self.ga_cfg),
            "tight": (_tighten_sa(self.sa_cfg), _tighten_ga(self.ga_cfg)),
        }
        self._queue: List[_Pending] = []
        # Exact tier: full-instance digest -> (perm, objective).
        self._cache: "OrderedDict[str, Tuple[np.ndarray, float]]" = OrderedDict()
        # Shape tier: (order, system-graph) digest -> latest perm; a hit
        # with different flows warm-starts the solve instead of serving it.
        self._shape_cache: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self.stats = EngineStats()
        self._lock = threading.RLock()          # queue / cache / stats
        self._cond = threading.Condition(self._lock)
        self._dispatch_lock = threading.Lock()  # serializes solves
        # engine.dispatch + engine.fetch seconds of the group being solved
        # (under the dispatch lock): its MapResponse.seconds, amortized
        self._device_s = 0.0
        self._flusher: Optional[threading.Thread] = None
        self._stop = False

    # ------------------------------------------------------------- plumbing
    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest configured bucket holding an order-n instance."""
        for b in self.buckets:
            if n <= b:
                return b
        return None                      # oversize: solved at exact size

    def large_bucket_for(self, n: int) -> Optional[int]:
        """Routing label for the multilevel path: the smallest large
        bucket holding an order-n instance, or the largest one for orders
        beyond it (multilevel has no size ceiling — the label only groups
        the wave).  None below ``multilevel_min_n``: small oversize
        instances keep the unpadded dense exact-size path."""
        if n < self.multilevel_min_n or not self._large_set:
            return None
        for b in self.large_buckets:
            if b in self._large_set and n <= b:
                return b
        return max(self._large_set)

    def _route(self, n: int) -> Optional[int]:
        """Bucket label for an order-n request: dense bucket first, then
        the multilevel large buckets, else None (exact-size path)."""
        b = self.bucket_for(n)
        return b if b is not None else self.large_bucket_for(n)

    def digest(self, req: MapRequest, algorithm: Optional[str] = None,
               tier: str = "default") -> str:
        """Exact-tier cache key: the instance and everything that shapes its
        solution (resolved algorithm + budget tier).  The seed is excluded
        by default -- repeated job shapes are served from cache regardless
        of the request's key -- unless the request opts in via
        ``cache_seed``.  Multilevel-routed orders fold the multilevel
        config in instead — that is what shapes their solve."""
        algorithm = algorithm or req.algorithm
        sa_cfg, ga_cfg = self._tier_cfgs[tier]
        h = hashlib.sha1()
        C = np.ascontiguousarray(req.C, dtype=np.float32)
        M = np.ascontiguousarray(req.M, dtype=np.float32)
        seed_part = f"|s{req.seed}" if req.cache_seed else ""
        n = C.shape[0]
        ml_part = ""
        if self.bucket_for(n) is None and self.large_bucket_for(n) is not None:
            ml_part = f"|ml|{self.multilevel_cfg}"
        h.update(f"{n}|{algorithm}|{tier}|{self.num_processes}|"
                 f"{self.polish_rounds}|{sa_cfg}|{ga_cfg}"
                 f"{seed_part}{ml_part}".encode())
        h.update(C.tobytes())
        h.update(M.tobytes())
        return h.hexdigest()

    def shape_digest(self, req: MapRequest) -> str:
        """Shape-tier key: order + system graph only (flows excluded), so a
        job of the same size on the same allocated topology is a near miss
        even when its traffic pattern differs."""
        M = np.ascontiguousarray(req.M, dtype=np.float32)
        h = hashlib.sha1()
        h.update(f"{M.shape[0]}|".encode())
        h.update(M.tobytes())
        return h.hexdigest()

    def _cache_get(self, key: str) -> Optional[Tuple[np.ndarray, float]]:
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
        return hit

    def _cache_put(self, key: str, shape_key: str, perm: np.ndarray,
                   objective: float) -> None:
        # Store a private copy: responses hand out arrays the caller may
        # mutate, and a poisoned entry would serve every future hit.
        perm = np.array(perm, copy=True)
        self._cache[key] = (perm, objective)
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)
        self._shape_cache[shape_key] = perm
        self._shape_cache.move_to_end(shape_key)
        while len(self._shape_cache) > self.cache_size:
            self._shape_cache.popitem(last=False)

    def _warm_perm(self, req: MapRequest) -> Optional[np.ndarray]:
        """Shape-tier near-miss lookup (call under the lock).

        ``cache_seed`` requests skip it so best-of-k restart sweeps stay
        independent solves rather than all descending from one seed.
        """
        if not self.warm_start or req.cache_seed or req.C.shape[0] < 2:
            return None
        return self._shape_cache.get(self.shape_digest(req))

    # --------------------------------------------------------------- warmup
    def _wave_sizes(self) -> Tuple[int, ...]:
        """Every instance-axis wave size the engine can dispatch: waves are
        padded to powers of two and chunked at ``max_batch``, so only
        {1, 2, 4, ..., next_pow2(max_batch)} programs exist per bucket."""
        max_wave = 1 << (self.max_batch - 1).bit_length()
        sizes, w = [], 1
        while w <= max_wave:
            sizes.append(w)
            w *= 2
        return tuple(sizes)

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               algorithms: Sequence[str] = ("psa",),
               tiers: Sequence[str] = ("default",),
               batch_sizes: Optional[Sequence[int]] = None,
               warm_starts: Sequence[bool] = (False, True),
               execute: Optional[bool] = None) -> int:
        """AOT-precompile bucket programs so first-wave requests stop
        paying XLA compile time in their mapping latency.

        For every (bucket, wave size, algorithm, tier, warm-start
        presence) combination this lowers and compiles the batched solver
        program — ``jit(...).lower().compile()`` — plus the batched
        polish, without executing a solve.  The compiled executables land
        in JAX's persistent compilation cache (enabled when
        ``JAX_COMPILATION_CACHE_DIR`` is set, as CI and the tier-1 run
        do), so the first real dispatch of each shape reloads them
        instead of recompiling; ``benchmarks/scheduler_sim.py --warmup``
        records the warm-vs-cold p99.

        ``execute`` additionally runs each program once on a dummy wave,
        which also fills the in-process jit dispatch cache; the default
        (``None``) turns execution on exactly when no persistent cache is
        configured — AOT executables alone cannot be reached by the
        normal dispatch path in that case.  With a ``mesh`` the sharded
        programs are warmed instead, matching :meth:`_dispatch`.

        Returns the number of programs compiled (also accumulated in
        ``stats.warmup_programs``).

        Only the dense padded buckets are warmed here.  The multilevel
        large buckets run programs fixed by each level's order
        (``multilevel.level_order``), so they compile on the first solve
        of each order's power of two and never again in the process (the
        persistent JAX compilation cache amortizes them across
        processes).
        """
        buckets = tuple(self.buckets if buckets is None else
                        sorted(int(b) for b in buckets))
        for b in buckets:
            if b not in self.buckets:
                raise ValueError(f"unknown bucket {b}; have {self.buckets}")
        for a in algorithms:
            if a not in ALGORITHMS:
                raise ValueError(f"algorithm must be one of {ALGORITHMS}")
        for t in tiers:
            if t not in TIERS:
                raise ValueError(f"tier must be one of {TIERS}")
        if batch_sizes is None:
            if not self.pad_batches:
                # Without pow2 padding the engine dispatches arbitrary wave
                # sizes; guessing here would compile unused programs while
                # real waves stay cold.
                raise ValueError(
                    "pad_batches=False: pass batch_sizes= explicitly")
            sizes = self._wave_sizes()
        else:
            sizes = tuple(int(b) for b in batch_sizes)
        if execute is None:
            execute = jax.config.jax_compilation_cache_dir is None
        # The persistent cache drops entries that compiled faster than its
        # min-compile-time threshold (1s by default) — which is precisely
        # the small-bucket/polish programs warmup exists to cover.  Cache
        # everything we AOT-compile, then restore the caller's threshold.
        prev_min = None
        if not execute:
            prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        count = 0
        try:
            for bucket in buckets:
                # event_width="auto": populate the measured width cache
                # eagerly (it is only *read* during tracing), so the
                # programs compiled below — and every later dispatch at
                # this bucket — resolve the tuned width instead of the
                # deterministic fallback.  The width never changes
                # results, so mixing tuned and fallback programs is safe.
                if any(self._tier_cfgs[t][0].event_width == "auto"
                       for t in tiers):
                    annealing.autotune_event_width(bucket)
                for wave in sizes:
                    count += self._warmup_polish(bucket, wave, execute)
                    for algorithm in algorithms:
                        for tier in tiers:
                            for warm in warm_starts:
                                count += self._warmup_solver(
                                    bucket, wave, algorithm, tier, warm,
                                    execute)
        finally:
            if prev_min is not None:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", prev_min)
        with self._lock:
            self.stats.warmup_programs += count
        return count

    def _dummy_wave(self, bucket: int, wave: int):
        """Well-formed dummy instances for lowering (and, without a
        persistent cache, executing) a bucket program."""
        rng = np.random.RandomState(0)
        A = rng.randint(1, 5, (bucket, bucket)).astype(np.float32)
        A = A + A.T
        np.fill_diagonal(A, 0)
        Cs = jnp.broadcast_to(jnp.asarray(A), (wave, bucket, bucket))
        Ms = Cs
        keys = jnp.zeros((wave, 2), jnp.uint32)
        nvs = jnp.full((wave,), bucket, jnp.int32)
        return Cs, Ms, keys, nvs

    def _warmup_solver(self, bucket: int, wave: int, algorithm: str,
                       tier: str, warm: bool, execute: bool) -> int:
        sa_cfg, ga_cfg = self._tier_cfgs[tier]
        Cs, Ms, keys, nvs = self._dummy_wave(bucket, wave)
        ips = None
        if warm:
            ips = jnp.broadcast_to(jnp.arange(bucket, dtype=jnp.int32),
                                   (wave, bucket))
        if self.mesh is not None:
            nshard = int(self.mesh.shape[self.instance_axis])
            Cs, Ms, keys, nvs, ips, _ = batch_sharded.pad_to_mesh_multiple(
                Cs, Ms, keys, nvs, ips, nshard)
            if algorithm == "pca":
                cfg = composite.CompositeConfig(sa=sa_cfg, ga=ga_cfg)
            else:
                cfg = sa_cfg if algorithm == "psa" else ga_cfg
            fn = batch_sharded._sharded_program(
                algorithm, cfg, self.num_processes, True, self.mesh,
                self.instance_axis, True, ips is not None)
            args = [Cs, Ms, keys, nvs] + ([ips] if ips is not None else [])
            if execute:
                jax.block_until_ready(fn(*args))
            else:
                fn.lower(*args).compile()
            return 1
        kw = dict(n_valid=nvs, init_perm=ips)
        if algorithm == "psa":
            fn, args = annealing.run_psa_batch, (Cs, Ms, keys, sa_cfg,
                                                 self.num_processes)
            kw["counts"] = _keeps_counts(sa_cfg, bucket)
        elif algorithm == "pga":
            fn, args = genetic.run_pga_batch, (Cs, Ms, keys, ga_cfg,
                                               self.num_processes)
        else:
            fn, args = composite.run_pca_batch, (
                Cs, Ms, keys, composite.CompositeConfig(sa=sa_cfg, ga=ga_cfg),
                self.num_processes)
        if execute:
            jax.block_until_ready(fn(*args, **kw))
        else:
            fn.lower(*args, **kw).compile()
        return 1

    def _warmup_polish(self, bucket: int, wave: int, execute: bool) -> int:
        if self.polish_rounds <= 0:
            return 0
        Cs, Ms, keys, nvs = self._dummy_wave(bucket, wave)
        ps = jnp.broadcast_to(jnp.arange(bucket, dtype=jnp.int32),
                              (wave, bucket))
        if self.mesh is not None:
            nshard = int(self.mesh.shape[self.instance_axis])
            Cs, Ms, keys, nvs, ps, _ = batch_sharded.pad_to_mesh_multiple(
                Cs, Ms, keys, nvs, ps, nshard)
            fn = batch_sharded._sharded_polish(
                self.polish_rounds, self.mesh, self.instance_axis)
            args = (Cs, Ms, ps, keys, nvs)
        else:
            fn = mapping_lib.polish_batch
            args = (Cs, Ms, ps, keys, self.polish_rounds, nvs)
        if execute:
            jax.block_until_ready(fn(*args))
        else:
            fn.lower(*args).compile()
        return 1

    # ------------------------------------------------------------------ API
    def submit(self, req: MapRequest) -> MapFuture:
        """Queue one request; non-blocking.  Returns the request's future,
        resolved by the background flusher (when started) or by the next
        explicit :meth:`flush`.

        With ``max_pending`` set, a submit finding the queue full is
        *rejected*: the returned future is already failed with
        :class:`QueueFull` (``stats.rejected`` counts them) and nothing is
        queued -- explicit backpressure instead of unbounded growth."""
        validate_request(req)
        algorithm, tier = self.policy.resolve(req.algorithm, req.deadline_ms)
        pending = _Pending(req=req, future=MapFuture(), algorithm=algorithm,
                           tier=tier, t_submit=time.monotonic())
        with self._cond:
            if (self.max_pending is not None
                    and len(self._queue) >= self.max_pending):
                self.stats.rejected += 1
                pending.future._fail(QueueFull(
                    f"engine queue at max_pending={self.max_pending}"))
                return pending.future
            self.stats.submitted += 1
            self._queue.append(pending)
            self._cond.notify_all()
        return pending.future

    def flush(self) -> Dict[str, MapResponse]:
        """Solve everything queued; returns {job_id: response}.  Safe to
        call with the flusher running -- each request is served exactly
        once (whoever drains it from the queue resolves its future)."""
        with self._cond:
            pending, self._queue = self._queue, []
        try:
            return self._flush_pending(pending, raise_errors=True)
        except BaseException as e:
            for p in pending:                # no future may be left hanging
                if not p.future.done():
                    p.future._fail(e)
            raise

    def map_one(self, C: np.ndarray, M: np.ndarray, algorithm: str = "psa",
                job_id: str = "job", seed: int = 0,
                cache_seed: bool = False,
                deadline_ms: Optional[float] = None) -> MapResponse:
        """Convenience single-request path (still padded + cached).  With
        the flusher running this blocks on the future; otherwise it flushes
        synchronously."""
        fut = self.submit(MapRequest(job_id=job_id, C=np.asarray(C),
                                     M=np.asarray(M), algorithm=algorithm,
                                     seed=seed, cache_seed=cache_seed,
                                     deadline_ms=deadline_ms))
        if not self.running:
            self.flush()
        return fut.result()

    # -------------------------------------------------------- async flusher
    @property
    def running(self) -> bool:
        return self._flusher is not None and self._flusher.is_alive()

    def start(self) -> "MappingEngine":
        """Start the background flusher thread (idempotent)."""
        with self._cond:
            if self.running:
                return self
            self._stop = False
            # created under the lock: two racing start() calls must not
            # each spawn a flusher (stop() could then only join one)
            self._flusher = threading.Thread(target=self._flush_loop,
                                             name="mapper-flusher",
                                             daemon=True)
            self._flusher.start()
        return self

    def stop(self, flush_pending: bool = True) -> None:
        """Stop the flusher; by default drain what is still queued so no
        future is left unresolved.

        The queue and the flusher handle are claimed *together with* the
        stop flag, under the lock.  The pre-fix ordering joined the
        flusher first and only drained afterwards, which raced concurrent
        ``start()``/``submit()`` calls: ``stop()`` could join (and hang
        on) a freshly-started flusher it never signalled, and a request
        queued during an in-flight ``_flush_pending`` sat in the queue
        until the racing drains happened to line up.  Claiming under the
        lock makes the hand-over atomic: once ``stop()`` holds the queue
        slice, it alone resolves those futures, and ``running`` is
        already False so later submitters fall back to synchronous
        ``flush()``.  With ``flush_pending=False`` the queue is left
        intact for a later explicit :meth:`flush`.
        """
        with self._cond:
            self._stop = True
            # Claim the flusher handle under the lock: a concurrent
            # start() can no longer swap in a thread we would join but
            # never signal.  The claimed thread notices it is no longer
            # self._flusher and exits without touching the queue.
            flusher, self._flusher = self._flusher, None
            drained: List[_Pending] = []
            if flush_pending:
                drained, self._queue = self._queue, []
            self._cond.notify_all()
        if flusher is not None:
            flusher.join()
        if flush_pending:
            try:
                self._flush_pending(drained, raise_errors=True)
            except BaseException as e:
                for p in drained:            # no future may be left hanging
                    if not p.future.done():
                        p.future._fail(e)
                raise
            # Final sweep: requests that raced in between the claim above
            # and the join are in the queue, not in ``drained``.
            self.flush()

    def __enter__(self) -> "MappingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _group_key(self, p: _Pending) -> Tuple[Optional[int], str, str]:
        return (self._route(p.req.C.shape[0]), p.algorithm, p.tier)

    def _take_ready_locked(self) -> Tuple[List[_Pending], Optional[float]]:
        """Pick the requests the flusher should dispatch now (caller holds
        the lock): every full group, plus every group holding a request
        older than the flush deadline.  Groups that are neither stay queued
        and keep batching -- a lone overdue straggler in one bucket must
        not degrade other buckets' waves.  Returns (ready,
        seconds_until_oldest_deadline); ready is empty while nothing is
        due."""
        if not self._queue:
            return [], None
        now = time.monotonic()
        deadline_s = self.flush_deadline_ms / 1000.0
        counts: Dict[Tuple[Optional[int], str, str], int] = {}
        overdue = set()
        for p in self._queue:
            k = self._group_key(p)
            counts[k] = counts.get(k, 0) + 1
            if now - p.t_submit >= deadline_s:
                overdue.add(k)
        full = {k for k, c in counts.items() if c >= self.max_batch}
        take = full | overdue
        if take:
            ready = [p for p in self._queue if self._group_key(p) in take]
            self._queue = [p for p in self._queue
                           if self._group_key(p) not in take]
            self.stats.full_bucket_flushes += len(full)
            self.stats.deadline_flushes += len(overdue - full)
            return ready, None
        oldest = min(p.t_submit for p in self._queue)
        return [], deadline_s - (now - oldest)

    def _flush_loop(self) -> None:
        me = threading.current_thread()
        while True:
            with self._cond:
                while (self._flusher is me and not self._stop
                       and not self._queue):
                    self._cond.wait()
                if self._flusher is not me or self._stop:
                    # stop() claimed the handle (and, with flush_pending,
                    # the queue) under the lock; whatever is still queued
                    # is stop()'s to serve, not ours.
                    return
                ready, wait_s = self._take_ready_locked()
                if not ready:
                    self._cond.wait(timeout=wait_s)
                    continue
            try:
                self._flush_pending(ready, raise_errors=False)
            except BaseException as e:       # never let the flusher die with
                for p in ready:              # unresolved futures behind it
                    if not p.future.done():
                        p.future._fail(e)

    # ---------------------------------------------------------- solve paths
    def _flush_pending(self, pending: List[_Pending], raise_errors: bool
                       ) -> Dict[str, MapResponse]:
        """Serve a drained slice of the queue: cache pass, grouped batched
        solves, future resolution.  The single code path used by both the
        synchronous ``flush()`` and the background flusher, so the two are
        bitwise-equivalent on the same drained set."""
        if not pending:
            return {}
        oldest = min(p.t_submit for p in pending)
        with telemetry.span(
                "engine.flush", jobs=[p.req.job_id for p in pending],
                requests=len(pending),
                queue_wait_ms=(time.monotonic() - oldest) * 1e3) as span:
            return self._serve(pending, raise_errors, span)

    def _serve(self, pending: List[_Pending], raise_errors: bool,
               span: telemetry.Span) -> Dict[str, MapResponse]:
        responses: Dict[str, MapResponse] = {}
        # Cache pass + group misses by (bucket, algorithm, tier); identical
        # instances inside one wave are solved once & shared.  Runs before
        # the dispatch lock so a pure cache hit is never serialized behind
        # an unrelated in-flight solve.
        groups: Dict[Tuple[Optional[int], str, str],
                     "OrderedDict[str, List[_Pending]]"] = {}
        hits = 0
        with self._lock:
            for p in pending:
                if p.future.done():          # cancelled while queued: skip
                    self.stats.cancelled += 1
                    continue
                key = self.digest(p.req, p.algorithm, p.tier)
                hit = self._cache_get(key)
                if hit is not None:
                    perm, objective = hit
                    self.stats.cache_hits += 1
                    hits += 1
                    resp = self._respond(
                        p, perm, objective,
                        bucket=self._route(p.req.C.shape[0]),
                        cached=True, seconds=0.0, batch_size=0)
                    if p.future._resolve(resp):
                        responses[p.req.job_id] = resp
                    else:                    # cancel won the claim race
                        self.stats.cancelled += 1
                    continue
                g = groups.setdefault(self._group_key(p), OrderedDict())
                g.setdefault(key, []).append(p)
        span.set(cache_hits=hits)
        if not groups:
            return responses
        with self._dispatch_lock:
            first_error: Optional[BaseException] = None
            for (bucket, algorithm, tier), by_digest in groups.items():
                heads = [ps[0] for ps in by_digest.values()]
                self._device_s = 0.0
                baselines = [None] * len(heads)
                try:
                    with self._lock:
                        warms = [self._warm_perm(p.req) for p in heads]
                    if bucket is None:
                        solved = [self._solve_exact(p.req, algorithm, tier, w)
                                  for p, w in zip(heads, warms)]
                    elif bucket in self._large_set:
                        # Multilevel path: per-head host-side coarsening +
                        # warm-started sparse refinement; shape-tier warm
                        # starts are ignored (the coarse solve is the seed).
                        results = [self._solve_multilevel(p.req)
                                   for p in heads]
                        solved = [r[:2] for r in results]
                        baselines = [r[2] for r in results]
                        warms = [None] * len(heads)
                    else:
                        solved = self._solve_bucket(
                            bucket, algorithm, tier,
                            [p.req for p in heads], warms)
                except Exception as e:       # fail this group's futures only
                    for ps in by_digest.values():
                        for p in ps:
                            p.future._fail(e)
                    first_error = first_error or e
                    continue
                total = sum(len(ps) for ps in by_digest.values())
                per_instance = self._device_s / max(total, 1)
                with telemetry.span("engine.respond"), self._lock:
                    self.stats.warm_starts += sum(w is not None
                                                  for w in warms)
                    for key, (perm, objective), w, base, p0 in zip(
                            by_digest, solved, warms, baselines, heads):
                        self._cache_put(key, self.shape_digest(p0.req),
                                        perm, objective)
                        for p in by_digest[key]:
                            resp = self._respond(
                                p, perm, objective, bucket=bucket,
                                cached=False, seconds=per_instance,
                                batch_size=total, warm_start=w is not None,
                                baseline=base)
                            if p.future._resolve(resp):
                                responses[p.req.job_id] = resp
                            else:            # cancelled mid-solve
                                self.stats.cancelled += 1
            if first_error is not None and raise_errors:
                raise first_error
        return responses

    def _respond(self, p: _Pending, perm: np.ndarray, objective: float,
                 bucket: Optional[int], cached: bool, seconds: float,
                 batch_size: int, warm_start: bool = False,
                 baseline: Optional[float] = None) -> MapResponse:
        """``baseline``, the identity's F, when the solve already has it
        (a multilevel solve's, over the flows' nonzeros); else it is
        computed here."""
        req = p.req
        n = req.C.shape[0]
        if baseline is None:
            baseline = float((np.asarray(req.C, np.float64)
                              * np.asarray(req.M, np.float64)).sum())
        if objective > baseline:
            # A mapping must never be worse than the trivial placement.
            perm, objective = np.arange(n, dtype=np.int32), baseline
        return MapResponse(job_id=req.job_id, perm=np.array(perm, copy=True),
                           objective=float(objective), baseline=baseline,
                           algorithm=p.algorithm, n=n, bucket=bucket,
                           cached=cached, seconds=seconds,
                           batch_size=batch_size, tier=p.tier,
                           warm_start=warm_start)

    def _init_perm_batch(self, reqs: List[MapRequest], bucket: int,
                         warms: List[Optional[np.ndarray]],
                         Bp: Optional[int] = None) -> Optional[np.ndarray]:
        """Warm-start rows padded to the bucket; all-(-1) rows mark cold
        instances (the solvers' no-warm sentinel) and cover any dummy
        batch-padding rows.  None when nothing in the batch has a near
        miss, keeping the cold path untouched."""
        if all(w is None for w in warms):
            return None
        ips = np.full((Bp or len(reqs), bucket), -1, np.int32)
        for i, (req, w) in enumerate(zip(reqs, warms)):
            if w is None:
                continue
            n = req.C.shape[0]
            ips[i, :n] = w
            ips[i, n:] = np.arange(n, bucket, dtype=np.int32)
        return ips

    def _solve_bucket(self, bucket: int, algorithm: str, tier: str,
                      reqs: List[MapRequest],
                      warms: List[Optional[np.ndarray]]
                      ) -> List[Tuple[np.ndarray, float]]:
        """Pad every request to ``bucket`` and dispatch one batched solve.

        The instance axis is itself padded to the next power of two and
        oversized waves are chunked at ``max_batch`` (``pad_batches``), so
        a long-lived service compiles at most log2(max_batch)+1 programs
        per bucket instead of one per distinct wave size; vmap rows are
        independent, so real rows are bitwise-unaffected and the dummy
        rows are dropped.
        """
        if self.pad_batches and len(reqs) > self.max_batch:
            out = []
            for i in range(0, len(reqs), self.max_batch):
                out.extend(self._solve_bucket(
                    bucket, algorithm, tier, reqs[i:i + self.max_batch],
                    warms[i:i + self.max_batch]))
            return out
        B = len(reqs)
        Bp = 1 << (B - 1).bit_length() if self.pad_batches else B
        # the rows the delta evaluation works on: real processes, padding
        # to the bucket, the kernel's lane padding, the dummy wave rows
        with telemetry.span(
                "engine.pack", bucket=bucket, rows=B, padded_rows=Bp,
                orders=sum(req.C.shape[0] for req in reqs),
                kernel_order=kernel_ops.delta_order(bucket)):
            Cs = np.zeros((Bp, bucket, bucket), np.float32)
            Ms = np.zeros((Bp, bucket, bucket), np.float32)
            nvs = np.zeros(Bp, np.int32)
            keys = []
            for i, req in enumerate(reqs):
                n = req.C.shape[0]
                Cs[i, :n, :n] = req.C
                Ms[i, :n, :n] = req.M
                nvs[i] = n
                keys.append(jax.random.PRNGKey(req.seed))
            for j in range(B, Bp):         # dummy rows replicate instance 0
                Cs[j], Ms[j], nvs[j] = Cs[0], Ms[0], nvs[0]
                keys.append(jax.random.PRNGKey(0))
            Cs_j, Ms_j = jnp.asarray(Cs), jnp.asarray(Ms)
            nvs_j = jnp.asarray(nvs)
            ips = self._init_perm_batch(reqs, bucket, warms, Bp)
            ips_j = None if ips is None else jnp.asarray(ips)
        with telemetry.span("engine.dispatch", algorithm=algorithm, tier=tier,
                            path="bucket",
                            delta_form=kernel_ops.delta_form(bucket)
                            ) as dispatch:
            perms, fs, counts = self._dispatch(
                algorithm, tier, Cs_j, Ms_j, jnp.stack(keys), nvs_j, ips_j)
            if self.polish_rounds > 0:
                # Same final 2-swap refinement find_mapping applies,
                # batched and mask-aware so swaps never cross the
                # valid/padded boundary; with a mesh it is sharded like
                # the solve.
                pkeys = jnp.stack([jax.random.fold_in(k, 7) for k in keys])
                if self.mesh is not None:
                    perms, fs = batch_sharded.polish_batch_sharded(
                        Cs_j, Ms_j, perms, pkeys, self.polish_rounds, nvs_j,
                        mesh=self.mesh, axis=self.instance_axis)
                else:
                    perms, fs = mapping_lib.polish_batch(
                        Cs_j, Ms_j, perms, pkeys, self.polish_rounds, nvs_j)
        with self._lock:
            self.stats.solver_batches += 1
            self.stats.solver_calls += B
        with telemetry.span("engine.fetch") as fetch:
            perms = np.asarray(perms)
            fs = np.asarray(fs)
            if counts is not None:
                fetch.set(**annealing.loop_totals(counts))
        self._device_s += dispatch.dur + fetch.dur
        out = []
        for i, req in enumerate(reqs):
            n = int(nvs[i])
            if n < 2:                      # degenerate: nothing to optimise
                f_id = float((np.asarray(req.C, np.float64)
                              * np.asarray(req.M, np.float64)).sum())
                out.append((np.arange(n, dtype=np.int32), f_id))
                continue
            # Feasibility invariant: the valid prefix is a permutation of
            # the real nodes; the padded tail is identity and is dropped.
            out.append((perms[i, :n].astype(np.int32), float(fs[i])))
        return out

    def _solve_exact(self, req: MapRequest, algorithm: str, tier: str,
                     warm: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, float]:
        """Oversize instances (> max bucket) run unpadded, one at a time
        (still warm-started from a shape-tier near miss when available)."""
        sa_cfg, ga_cfg = self._tier_cfgs[tier]
        C = jnp.asarray(req.C, jnp.float32)
        M = jnp.asarray(req.M, jnp.float32)
        key = jax.random.PRNGKey(req.seed)
        ip = None if warm is None else jnp.asarray(warm, jnp.int32)
        with telemetry.span("engine.dispatch", algorithm=algorithm, tier=tier,
                            path="exact") as dispatch:
            if algorithm == "psa":
                p, f, _ = annealing.run_psa(C, M, key, sa_cfg,
                                            self.num_processes, init_perm=ip)
            elif algorithm == "pga":
                p, f, _ = genetic.run_pga(C, M, key, ga_cfg,
                                          self.num_processes, init_perm=ip)
            else:
                p, f, _ = composite.run_pca(
                    C, M, key, composite.CompositeConfig(
                        sa=sa_cfg, ga=ga_cfg), self.num_processes,
                    init_perm=ip)
            if self.polish_rounds > 0:
                p, f = mapping_lib.polish(C, M, p, jax.random.fold_in(key, 7),
                                          self.polish_rounds)
        with self._lock:
            self.stats.solver_batches += 1
            self.stats.solver_calls += 1
        with telemetry.span("engine.fetch") as fetch:
            p, f = np.asarray(p, np.int32), float(f)
        self._device_s += dispatch.dur + fetch.dur
        return p, f

    def _solve_multilevel(self, req: MapRequest
                          ) -> Tuple[np.ndarray, float, float]:
        """Large-bucket instances run the coarsen → map → refine pipeline
        (``core.multilevel``): host-side heavy-edge coarsening of any
        order, dense coarse solve, warm-started *sparse* refinement per
        level — O(nnz) per candidate, so orders far beyond the dense
        buckets stay schedulable — each level on programs fixed by its
        order.  The tier's solver budgets do not apply;
        ``multilevel_cfg`` governs (and is folded into the cache digest
        for these orders).  The pipeline's waits for the device are
        ``engine.fetch`` spans inside this dispatch's span.  Returns the
        perm, its F and the identity's F (the response's baseline)."""
        n = req.C.shape[0]
        # the finest level's device order, and its lanes on the kernel path
        with telemetry.span(
                "engine.pack", bucket=self._route(n), rows=1, padded_rows=1,
                orders=n, kernel_order=kernel_ops.sparse_delta_order(
                    multilevel.level_order(n))):
            C = np.asarray(req.C, np.float32)
            M = np.asarray(req.M, np.float32)
        with telemetry.span("engine.dispatch", path="multilevel") as dispatch:
            res = multilevel.solve_multilevel(
                C, M, jax.random.PRNGKey(req.seed), self.multilevel_cfg,
                span=telemetry.span)
            out = (np.asarray(res.perm, np.int32), float(res.objective),
                   float(res.identity_objective))
        with self._lock:
            self.stats.solver_batches += 1
            self.stats.solver_calls += 1
        self._device_s += dispatch.dur
        return out

    def _dispatch(self, algorithm: str, tier: str, Cs, Ms, keys, nvs, ips):
        """Enqueue a bucket wave's solve: (perms, fs, the event loop's
        ``LoopCounts`` or None).  Only the PSA event loop on one device
        keeps counts."""
        sa_cfg, ga_cfg = self._tier_cfgs[tier]
        if self.mesh is not None:
            return self._dispatch_sharded(algorithm, sa_cfg, ga_cfg,
                                          Cs, Ms, keys, nvs, ips) + (None,)
        if algorithm == "psa":
            counts = _keeps_counts(sa_cfg, Cs.shape[-1])
            p, f, _, *tally = annealing.run_psa_batch(
                Cs, Ms, keys, sa_cfg, self.num_processes, n_valid=nvs,
                init_perm=ips, counts=counts)
            return p, f, tally[0] if counts else None
        if algorithm == "pga":
            p, f, _ = genetic.run_pga_batch(Cs, Ms, keys, ga_cfg,
                                            self.num_processes, n_valid=nvs,
                                            init_perm=ips)
        else:
            p, f, _ = composite.run_pca_batch(
                Cs, Ms, keys, composite.CompositeConfig(
                    sa=sa_cfg, ga=ga_cfg),
                self.num_processes, n_valid=nvs, init_perm=ips)
        return p, f, None

    def _dispatch_sharded(self, algorithm: str, sa_cfg, ga_cfg,
                          Cs, Ms, keys, nvs, ips):
        """Mesh path: same wave, instance axis sharded over the mesh axis.
        ``batch_sharded`` pads the wave to a multiple of the axis size and
        trims the dummy rows, so callers see identical shapes and values."""
        if algorithm == "psa":
            p, f, _ = batch_sharded.run_psa_batch_sharded(
                Cs, Ms, keys, sa_cfg, self.num_processes, n_valid=nvs,
                init_perm=ips, mesh=self.mesh, axis=self.instance_axis)
        elif algorithm == "pga":
            p, f, _ = batch_sharded.run_pga_batch_sharded(
                Cs, Ms, keys, ga_cfg, self.num_processes, n_valid=nvs,
                init_perm=ips, mesh=self.mesh, axis=self.instance_axis)
        else:
            p, f, _ = batch_sharded.run_pca_batch_sharded(
                Cs, Ms, keys, composite.CompositeConfig(
                    sa=sa_cfg, ga=ga_cfg),
                self.num_processes, n_valid=nvs, init_perm=ips,
                mesh=self.mesh, axis=self.instance_axis)
        return p, f
