"""Mesh-distributed forms of the three mapping algorithms.

The paper's MPI processes map onto mesh devices via ``shard_map``
(docs/DESIGN.md §4): one device = one SA solver group / GA island.
Exchanges use JAX-native collectives instead of MPI:

  * PSA best-broadcast   -> ``lax.all_gather`` of (best_f, best_p) + argmin;
  * PGA ring migration   -> ``lax.ppermute`` with the ring permutation -- an
    ICI-neighbour pattern that is cheaper on a TPU torus than on a switched
    cluster fabric;
  * final reduction      -> all_gather + argmin.

These functions are what ``launch/placement.py`` runs *on the job's own
devices* before the job starts -- exactly the paper's deployment model (the
mapping search runs on the allocated nodes themselves).

The per-device solver bodies reuse ``annealing._chain_round``, so every
mesh-distributed SA round runs the same acceptance-event hot loop (wide
batched delta evaluation through ``kernels.ops``) as the single-host path.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import annealing, genetic

Array = jax.Array


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _global_argmin(axis: str, f: Array, p: Array) -> Tuple[Array, Array]:
    """Global best (f, p) across a mesh axis (inside shard_map)."""
    fs = jax.lax.all_gather(f, axis)           # (procs,)
    ps = jax.lax.all_gather(p, axis)           # (procs, N)
    i = jnp.argmin(fs)
    return fs[i], ps[i]


# ----------------------------------------------------------------------------
# PSA over a mesh axis
# ----------------------------------------------------------------------------

def run_psa_mesh(C: Array, M: Array, key: Array, cfg: annealing.SAConfig,
                 mesh: Mesh, axis: str = "proc"
                 ) -> Tuple[Array, Array, Array]:
    """Parallel simulated annealing, one solver group per device on ``axis``."""
    nproc = mesh.shape[axis]

    def device_fn(keys):       # keys: (1, 2) slice of per-process keys
        key = keys[0]
        kinit, kbeta, krun = jax.random.split(key, 3)
        beta = annealing.make_beta(C, M, kbeta, cfg)
        chain_keys = jax.random.split(kinit, cfg.solvers)
        state = jax.vmap(lambda k: annealing.init_chain(C, M, k, cfg))(chain_keys)

        def round_step(st, k):
            rkeys = jax.random.split(k, cfg.solvers)
            st = jax.vmap(lambda s, kk: annealing._chain_round(
                C, M, s, kk, cfg, beta))(st, rkeys)
            # local best -> global best via all-gather + argmin
            li = jnp.argmin(st.best_f)
            gf, gp = _global_argmin(axis, st.best_f[li], st.best_p[li])
            bp = jnp.broadcast_to(gp, st.p.shape)
            bf = jnp.broadcast_to(gf, st.f.shape)
            st = annealing._adopt_best(st, bp, bf)
            return st, gf

        round_keys = jax.random.split(krun, cfg.num_exchanges)
        state, hist = jax.lax.scan(round_step, state, round_keys)
        li = jnp.argmin(state.best_f)
        gf, gp = _global_argmin(axis, state.best_f[li], state.best_p[li])
        return gp[None], gf[None], hist[None]

    keys = jax.random.split(key, nproc)
    spec = P(axis)
    fn = shard_map(device_fn, mesh=mesh, in_specs=(spec,),
                   out_specs=(spec, spec, spec), check_vma=False)
    ps, fs, hist = jax.jit(fn)(keys)
    i = jnp.argmin(fs)
    return ps[i], fs[i], hist.min(axis=0)


# ----------------------------------------------------------------------------
# PGA over a mesh axis (ring migration via ppermute)
# ----------------------------------------------------------------------------

def run_pga_mesh(C: Array, M: Array, key: Array, cfg: genetic.GAConfig,
                 mesh: Mesh, axis: str = "proc"
                 ) -> Tuple[Array, Array, Array]:
    nproc = mesh.shape[axis]
    ring = _ring_perm(nproc)

    def device_fn(keys):
        key = keys[0]
        kinit, krun = jax.random.split(key)
        state = genetic.init_island(C, M, kinit, cfg)

        def gen_step(st, k):
            st = genetic.breed(C, M, st, k, cfg)
            bp, bf = genetic.island_best(st)
            mig_p = jax.lax.ppermute(bp, axis, ring)
            mig_f = jax.lax.ppermute(bf, axis, ring)
            st = genetic.receive_migrants(st, mig_p, mig_f)
            gf = jax.lax.pmin(bf, axis)
            return st, gf

        gen_keys = jax.random.split(krun, cfg.generations)
        state, hist = jax.lax.scan(gen_step, state, gen_keys)
        bp, bf = genetic.island_best(state)
        gf, gp = _global_argmin(axis, bf, bp)
        return gp[None], gf[None], hist[None]

    keys = jax.random.split(key, nproc)
    spec = P(axis)
    fn = shard_map(device_fn, mesh=mesh, in_specs=(spec,),
                   out_specs=(spec, spec, spec), check_vma=False)
    ps, fs, hist = jax.jit(fn)(keys)
    i = jnp.argmin(fs)
    return ps[i], fs[i], hist.min(axis=0)


# ----------------------------------------------------------------------------
# Composite over a mesh axis
# ----------------------------------------------------------------------------

def run_pca_mesh(C: Array, M: Array, key: Array, cfg,
                 mesh: Mesh, axis: str = "proc"
                 ) -> Tuple[Array, Array, Array]:
    """Composite: per-device SA seeding (no exchange) + PGA with ppermute ring."""
    from . import composite as composite_mod
    nproc = mesh.shape[axis]
    ring = _ring_perm(nproc)
    n = C.shape[0]
    solvers = composite_mod._resolve_solvers(cfg, n)
    sa_cfg = annealing.SAConfig(**{**cfg.sa.__dict__, "solvers": solvers})

    def device_fn(keys):
        key = keys[0]
        kseed, kbeta, krun = jax.random.split(key, 3)
        beta = annealing.make_beta(C, M, kbeta, sa_cfg)
        chain_keys = jax.random.split(kseed, solvers)
        st_sa = jax.vmap(lambda k: annealing.init_chain(C, M, k, sa_cfg))(chain_keys)

        def sa_round(st, k):
            rkeys = jax.random.split(k, solvers)
            st = jax.vmap(lambda s, kk: annealing._chain_round(
                C, M, s, kk, sa_cfg, beta))(st, rkeys)
            return st, None   # NO exchange: populations stay unique (paper S3)

        round_keys = jax.random.split(krun, sa_cfg.num_exchanges)
        st_sa, _ = jax.lax.scan(sa_round, st_sa, round_keys)
        state = genetic.GAState(pop=st_sa.best_p, fit=st_sa.best_f)

        def gen_step(st, k):
            st = genetic.breed(C, M, st, k, cfg.ga)
            bp, bf = genetic.island_best(st)
            mig_p = jax.lax.ppermute(bp, axis, ring)
            mig_f = jax.lax.ppermute(bf, axis, ring)
            st = genetic.receive_migrants(st, mig_p, mig_f)
            gf = jax.lax.pmin(bf, axis)
            return st, gf

        gen_keys = jax.random.split(jax.random.fold_in(krun, 1), cfg.ga.generations)
        state, hist = jax.lax.scan(gen_step, state, gen_keys)
        bp, bf = genetic.island_best(state)
        gf, gp = _global_argmin(axis, bf, bp)
        return gp[None], gf[None], hist[None]

    keys = jax.random.split(key, nproc)
    spec = P(axis)
    fn = shard_map(device_fn, mesh=mesh, in_specs=(spec,),
                   out_specs=(spec, spec, spec), check_vma=False)
    ps, fs, hist = jax.jit(fn)(keys)
    i = jnp.argmin(fs)
    return ps[i], fs[i], hist.min(axis=0)
