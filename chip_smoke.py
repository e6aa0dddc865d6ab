#!/usr/bin/env python3
"""Bring-up smoke of the mapping service on one TPU chip.

Drives the served path -- ``ResourceManager`` -> ``MappingEngine`` ->
PSA/PGA/PCA -> Pallas kernels -- once at deployment size and checks what
comes out:

1. ``kernels``: each served kernel (dense objective and swap delta in
   their instance-batched form, sparse objective and delta in their
   shared form) at n = 32, 128 and its cap (dense 768, sparse 4096)
   against its jnp reference, both on the chip, on integer instances:
   the two must agree bitwise and equal the float64 numpy value.
2. ``solvers``: one wave of 8 bucket-128 instances per algorithm through
   the engine, once on the kernels and once on the jnp dispatch: the
   mappings and objectives must be bitwise equal, and each algorithm's
   compiled batched program must hold a Pallas kernel
   (``tpu_custom_call``).
3. ``replay``: a seeded trace of 24 jobs of 32/64/128 processes plus one
   of 512 and one of 1024 (multilevel) through
   ``ResourceManager(M, candidates=3)`` on a 16x16x16 grid of 4096 nodes,
   all three algorithms, default budgets.  Every job must commit a valid
   permutation whose objective a numpy recomputation confirms, none
   degraded, and the mean mapped objective must be no worse than a
   first-fit replay of the same trace.
4. ``torus``: a known-optimum torus instance of order 1024 through the
   engine's multilevel path: reported F equals numpy F, and F >= F0.

Each phase prints its wall time split into compile time (tracing,
lowering and backend compiles) and the rest ("steady": device work and
the host-side checks), the number of compiles and persistent-cache
hits, and the device's peak memory so far.  The last line of standard
output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every check passed.  Without a TPU the script
exits nonzero before any phase.

    python chip_smoke.py             # one chip: the four phases above
    python chip_smoke.py --chips 4   # only: a bucket-128 wave of 16
                                     # sharded over 4 chips vs one chip

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or else lives in
``<checkout>/.jax_cache`` (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (annealing, batch_sharded, composite,  # noqa: E402
                        exact, genetic, instances, sparse)
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.qap_delta import (  # noqa: E402
    ROW_FORM_MAX_N, qap_delta_pallas_batch, qap_delta_rows_pallas_batch)
from repro.kernels.qap_objective import (  # noqa: E402
    MAX_KERNEL_N, qap_objective_pallas_batch)
from repro.kernels.qap_sparse import (  # noqa: E402
    MAX_SPARSE_KERNEL_N, qap_delta_sparse_pallas_batch,
    qap_objective_sparse_pallas_batch)
from repro.launch.mesh import make_instance_mesh  # noqa: E402
from repro.serve import (JobSpec, MappingEngine, MapRequest,  # noqa: E402
                         ResourceManager, default_flows, synthetic_trace)
from repro.serve.mapper import ALGORITHMS  # noqa: E402

MACHINE = (16, 16, 16)                 # 4096-node system graph
EXACT_F32 = 1 << 24                    # integers below this are exact in f32

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Meter:
    """Per-phase wall time, compile time and count (from JAX's monitoring
    events: tracing, lowering and backend compiles), persistent-cache
    hits, and the device's peak memory.

    An event reports its duration when it ends, so it covers
    ``[now - duration, now]``; traces of nested jits nest, so compile
    time is the length of the union of those intervals."""

    def __init__(self):
        self.spans = []
        self.backend = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            now = time.perf_counter()
            self.spans.append((now - duration, now))
        if event == _BACKEND_EVENT:
            self.backend += 1
        elif event == _CACHE_HIT_EVENT:
            self.hits += 1

    def _compile_s(self, since: int) -> float:
        total, end = 0.0, float("-inf")
        for a, b in sorted(self.spans[since:]):
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    @contextlib.contextmanager
    def phase(self, name: str):
        s0, b0, h0 = len(self.spans), self.backend, self.hits
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        compile_s = self._compile_s(s0)
        hits = self.hits - h0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"phase {name}: wall {wall:.3f} s = compile {compile_s:.3f} s"
              f" + steady {wall - compile_s:.3f} s; "
              f"{self.backend - b0 - hits} compiles, {hits} cache hits; "
              f"peak device memory "
              + ("not reported" if peak is None
                 else f"{peak / 2**20:.1f} MiB"), flush=True)


# ------------------------------------------------------------------ inputs
@functools.lru_cache(maxsize=None)
def machine(dims=MACHINE) -> np.ndarray:
    return instances.grid_distance_matrix(dims)


def np_objective(C, M, perm) -> float:
    p = np.asarray(perm)
    return float((np.asarray(C, np.float64)
                  * np.asarray(M, np.float64)[np.ix_(p, p)]).sum())


def np_swapped(perm, a: int, b: int) -> np.ndarray:
    q = np.array(perm, copy=True)
    q[a], q[b] = q[b], q[a]
    return q


def random_pairs(rng, n: int, shape) -> np.ndarray:
    a = rng.integers(0, n, shape)
    b = (a + rng.integers(1, n, shape)) % n        # b != a
    return np.stack([a, b], axis=-1).astype(np.int32)


def dense_instance(n: int, seed: int):
    """Integer instance: the resource manager's default flows on the first
    n nodes of the machine."""
    M = machine()[:n, :n]
    C = default_flows(n, seed)
    check(float(np.abs(C).sum() * M.max()) < EXACT_F32,
          f"n={n}: instance too heavy for exact f32 sums")
    return C, M


def wave_requests(n_max: int, count: int, seed: int, algorithm: str):
    """``count`` integer instances of orders in (n_max/2, n_max] on
    distinct slices of the machine, one engine wave."""
    rng = np.random.default_rng(seed)
    M_all = machine()
    reqs = []
    for i in range(count):
        n = int(rng.integers(n_max // 2 + 1, n_max + 1))
        nodes = np.sort(rng.choice(M_all.shape[0], n, replace=False))
        reqs.append(MapRequest(job_id=f"w{i}", C=default_flows(n, seed + i),
                               M=M_all[np.ix_(nodes, nodes)],
                               algorithm=algorithm, seed=seed + i))
    return reqs


def solve_wave(engine: MappingEngine, reqs):
    futs = [engine.submit(r) for r in reqs]
    engine.flush()
    out = [f.result() for f in futs]
    check(engine.stats.solver_batches == 1,
          f"wave took {engine.stats.solver_batches} dispatches, not one")
    return out


def same_mappings(got, want, what: str) -> None:
    for g, w in zip(got, want):
        check(np.array_equal(g.perm, w.perm) and g.objective == w.objective,
              f"{what}: {g.job_id} differs ({g.objective} vs {w.objective})")


@contextlib.contextmanager
def jnp_dispatch():
    """Route every QAP evaluation to the jnp references, as on a CPU
    backend.  The dispatch is decided while tracing, so the jit caches are
    cleared on the way in and out."""
    saved = ops._on_tpu
    jax.clear_caches()
    ops._on_tpu = lambda: False
    try:
        yield
    finally:
        ops._on_tpu = saved
        jax.clear_caches()


# ------------------------------------------------------------------ phases
def kernel_phase(dense_orders=(32, 128, MAX_KERNEL_N),
                 torus_dims=((2, 4, 4), (4, 4, 8), MACHINE), perms=4,
                 pairs=16, seed=0) -> None:
    """Every served kernel against its jnp reference on the chip."""
    rng = np.random.default_rng(seed)
    for n in dense_orders:
        insts = [dense_instance(n, seed + i) for i in range(2)]
        Cs = jnp.asarray(np.stack([c for c, _ in insts]))
        Ms = jnp.asarray(np.stack([m for _, m in insts]))
        P = np.stack([[rng.permutation(n) for _ in range(perms)]
                      for _ in range(2)]).astype(np.int32)   # (2, P, n)
        got = np.asarray(qap_objective_pallas_batch(Cs, Ms, jnp.asarray(P)))
        want = np.asarray(jax.jit(jax.vmap(ref.qap_objective_ref))(
            Cs, Ms, jnp.asarray(P)))
        exact_f = np.array([[np_objective(*insts[i], P[i, j])
                             for j in range(perms)] for i in range(2)])
        check(np.array_equal(got, want) and np.array_equal(got, exact_f),
              f"dense objective n={n}: kernel {got} ref {want} "
              f"numpy {exact_f}")

        ps = P.reshape(-1, n)                                 # (2P, n)
        pr = random_pairs(rng, n, (ps.shape[0], pairs))
        got = np.asarray(qap_delta_pallas_batch(Cs, Ms, jnp.asarray(ps),
                                                jnp.asarray(pr)))
        rows = ps.shape[0] // 2
        want = np.asarray(jax.jit(jax.vmap(
            lambda c, m, p, q: ref.qap_delta_ref(c, m, p, q)))(
                Cs, Ms, jnp.asarray(ps.reshape(2, rows, n)),
                jnp.asarray(pr.reshape(2, rows, pairs, 2)))).reshape(got.shape)
        C0, M0 = insts[0]
        exact_d = [np_objective(C0, M0, np_swapped(ps[0], a, b))
                   - np_objective(C0, M0, ps[0]) for a, b in pr[0]]
        check(np.array_equal(got, want) and np.array_equal(got[0], exact_d),
              f"dense delta n={n}: kernel and reference differ")
        if n <= ROW_FORM_MAX_N:
            rows = np.asarray(qap_delta_rows_pallas_batch(
                Cs, Ms, jnp.asarray(ps), jnp.asarray(pr)))
            check(rows.tobytes() == got.tobytes(),
                  f"dense delta n={n}: row and candidate forms differ")
        print(f"  dense n={n}: objective and delta match bitwise", flush=True)

    for dims in torus_dims:
        inst = exact.make_torus(dims)
        n = inst.n
        S = sparse.from_dense(inst.C)
        M = jnp.asarray(inst.M)
        P = np.stack([rng.permutation(n) for _ in range(perms)]
                     ).astype(np.int32)[None]                 # (1, P, n)
        got = np.asarray(qap_objective_sparse_pallas_batch(S, M,
                                                           jnp.asarray(P)))
        want = np.asarray(jax.jit(ref.qap_objective_sparse_ref)(
            S, M, jnp.asarray(P)))
        exact_f = np.array([[np_objective(inst.C, inst.M, p) for p in P[0]]])
        check(np.array_equal(got, want) and np.array_equal(got, exact_f),
              f"sparse objective n={n}: kernel {got} ref {want} "
              f"numpy {exact_f}")

        ps = P[0]
        pr = random_pairs(rng, n, (perms, pairs))
        got = np.asarray(qap_delta_sparse_pallas_batch(
            S, M, jnp.asarray(ps), jnp.asarray(pr)))
        want = np.asarray(jax.jit(ref.qap_delta_sparse_ref)(
            S, M, jnp.asarray(ps), jnp.asarray(pr)))
        exact_d = [np_objective(inst.C, inst.M, np_swapped(ps[0], a, b))
                   - np_objective(inst.C, inst.M, ps[0]) for a, b in pr[0]]
        check(np.array_equal(got, want) and np.array_equal(got[0], exact_d),
              f"sparse delta n={n}: kernel and reference differ")
        print(f"  sparse n={n} (d={S.max_degree}): objective and delta "
              f"match bitwise", flush=True)


def solver_programs_have_kernels(engine: MappingEngine, reqs) -> None:
    """``tpu_custom_call`` in each algorithm's compiled batched program at
    the wave's bucket."""
    bucket = engine.bucket_for(max(r.C.shape[0] for r in reqs))
    B = len(reqs)
    Cs = jnp.zeros((B, bucket, bucket), jnp.float32)
    keys = jnp.zeros((B, 2), jnp.uint32)
    nvs = jnp.full((B,), bucket, jnp.int32)
    nproc = engine.num_processes
    cca = composite.CompositeConfig(sa=engine.sa_cfg, ga=engine.ga_cfg)
    lowered = {
        "psa": annealing.run_psa_batch.lower(Cs, Cs, keys, engine.sa_cfg,
                                             nproc, n_valid=nvs),
        "pga": genetic.run_pga_batch.lower(Cs, Cs, keys, engine.ga_cfg,
                                           nproc, n_valid=nvs),
        "pca": composite.run_pca_batch.lower(Cs, Cs, keys, cca, nproc,
                                             n_valid=nvs),
    }
    for name, lo in lowered.items():
        check("tpu_custom_call" in lo.compile().as_text(),
              f"{name}: no Pallas kernel in the bucket-{bucket} program")
    print(f"  tpu_custom_call in the psa/pga/pca bucket-{bucket} programs",
          flush=True)


def solver_phase(n_max=128, wave=8, seed=11, engine_kwargs=None) -> None:
    """Whole-solver equality: kernels vs the jnp dispatch, per algorithm."""
    engine_kwargs = engine_kwargs or {}
    waves = {a: wave_requests(n_max, wave, seed, a) for a in ALGORITHMS}
    solver_programs_have_kernels(MappingEngine(**engine_kwargs),
                                 waves["psa"])
    on_kernels = {a: solve_wave(MappingEngine(**engine_kwargs), waves[a])
                  for a in ALGORITHMS}
    with jnp_dispatch():
        on_jnp = {a: solve_wave(MappingEngine(**engine_kwargs), waves[a])
                  for a in ALGORITHMS}
    for a in ALGORITHMS:
        same_mappings(on_kernels[a], on_jnp[a], f"{a} kernels vs jnp")
        for r, q in zip(on_kernels[a], waves[a]):
            check(r.objective == np_objective(q.C, q.M, r.perm),
                  f"{a}: {r.job_id} objective is not F(perm)")
        mean_f = np.mean([r.objective for r in on_kernels[a]])
        print(f"  {a}: wave of {wave} at bucket {r.bucket} bitwise equal "
              f"on kernels and jnp dispatch, mean F {mean_f}", flush=True)


def replay_trace(sizes=(32, 64, 128), jobs=24, large=(512, 1024), seed=3):
    """Seeded jobs cycling through the three algorithms, plus the large
    (multilevel-routed) ones in the middle of the trace."""
    specs = synthetic_trace(jobs, sizes=sizes, arrival_rate=2.0,
                            mean_run_s=20.0, seed=seed)
    specs = [dataclasses.replace(s, algorithm=ALGORITHMS[i % 3])
             for i, s in enumerate(specs)]
    mid = specs[len(specs) // 2].arrival_s
    specs += [JobSpec(job_id=f"big{n}", size=n, run_s=30.0,
                      arrival_s=mid + 0.01 * i, seed=seed + n)
              for i, n in enumerate(large)]
    return specs


def replay(M, specs, engine_kwargs, **rm_kwargs):
    rm = ResourceManager(M, MappingEngine(**engine_kwargs), **rm_kwargs)
    for s in specs:
        rm.submit_job(s)
    return rm, rm.run()


def replay_phase(grid=MACHINE, engine_kwargs=None, **trace_kwargs) -> None:
    """The resource manager at deployment size, against first-fit."""
    engine_kwargs = engine_kwargs or {}
    M = machine(grid)
    specs = replay_trace(**trace_kwargs)
    rm, rep = replay(M, specs, engine_kwargs, candidates=3)
    check(rep.jobs == len(specs), f"{len(specs) - rep.jobs} jobs unfinished")
    for h in rm.handles:
        r = h.result()
        nodes = h.allocation.nodes
        check(not r.degraded, f"{h.job_id}: degraded response")
        check(np.array_equal(np.sort(r.perm), np.arange(h.spec.size)),
              f"{h.job_id}: not a permutation")
        f = np_objective(h.C, M[np.ix_(nodes, nodes)], r.perm)
        check(r.objective == f,
              f"{h.job_id}: reported F {r.objective} != numpy F {f}")
    print(f"  co-opt: {rep.jobs} jobs on {M.shape[0]} nodes, all committed "
          f"valid, objectives confirmed; mean F {rep.mean_objective}, "
          f"makespan {rep.makespan_s} s, {rep.backfilled} backfilled, "
          f"map wall p50/p99 {rep.map_wall_p50_ms}/{rep.map_wall_p99_ms} ms",
          flush=True)
    _, ff = replay(M, specs, engine_kwargs, candidates=1,
                   policies=("first_fit",))
    check(rep.mean_objective <= ff.mean_objective,
          f"co-opt mean F {rep.mean_objective} worse than first-fit "
          f"{ff.mean_objective}")
    print(f"  first-fit: mean F {ff.mean_objective} (co-opt/first-fit "
          f"{rep.mean_objective / ff.mean_objective})", flush=True)


def torus_phase(dims=(16, 8, 8), engine_kwargs=None) -> None:
    """Known optimum through the multilevel path."""
    inst = exact.make_torus(dims)
    r = MappingEngine(**(engine_kwargs or {})).map_one(inst.C, inst.M)
    f = np_objective(inst.C, inst.M, r.perm)
    check(np.array_equal(np.sort(r.perm), np.arange(inst.n)),
          "torus: not a permutation")
    check(r.objective == f, f"torus: reported F {r.objective} != {f}")
    check(f >= inst.optimum, f"torus: F {f} below the optimum {inst.optimum}")
    print(f"  torus n={inst.n}: F {f}, F0 {inst.optimum}, "
          f"F/F0 {f / inst.optimum}", flush=True)


def four_chip_phase(n_max=128, wave=16, seed=21, engine_kwargs=None) -> None:
    """One wave sharded over four chips vs the same wave on one."""
    engine_kwargs = engine_kwargs or {}
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(devices)}")
    print(f"  devices: {len(devices)} x {devices[0].device_kind}")
    mesh = make_instance_mesh(4)
    for a in ALGORITHMS:
        reqs = wave_requests(n_max, wave, seed, a)
        sharded = solve_wave(MappingEngine(mesh=mesh, **engine_kwargs), reqs)
        single = solve_wave(MappingEngine(**engine_kwargs), reqs)
        same_mappings(sharded, single, f"{a} sharded vs one device")
        print(f"  {a}: wave of {wave} bitwise equal on 4 chips and on one",
              flush=True)
    # Placement, from the compiled program the sharded engine runs: each
    # chip must hold its own quarter of the wave.
    eng = MappingEngine(mesh=mesh, **engine_kwargs)
    bucket = eng.bucket_for(n_max)
    prog = batch_sharded._sharded_program(
        "psa", eng.sa_cfg, eng.num_processes, True, mesh,
        batch_sharded.DEFAULT_AXIS, True, False)
    Cs = jnp.zeros((wave, bucket, bucket), jnp.float32)
    args = (Cs, Cs, jnp.zeros((wave, 2), jnp.uint32),
            jnp.full((wave,), bucket, jnp.int32))
    compiled = prog.lower(*args).compile()
    for x in compiled(*args):
        shards = x.addressable_shards
        check(len(x.sharding.device_set) == 4
              and {s.device for s in shards} == set(devices)
              and all(s.data.shape[0] == wave // 4 for s in shards),
              f"output {x.shape} placed as {x.sharding}, not a quarter "
              f"of the wave per chip")
    print(f"  placement: outputs sharded {compiled.output_shardings[0].spec}"
          f" over {len(devices)} devices, {wave // 4} instances each",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the instance-sharded wave on four "
                         "chips against one chip")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r})",
              file=sys.stderr)
        return 2
    meter = Meter()
    if args.chips == 4:
        with meter.phase("four_chips"):
            four_chip_phase()
    else:
        for name, fn in (("kernels", kernel_phase),
                         ("solvers", solver_phase),
                         ("replay", replay_phase),
                         ("torus", torus_phase)):
            with meter.phase(name):
                fn()
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
