"""Mesh-sharded instance dispatch for the batched mapping solvers.

``run_psa_batch`` / ``run_pga_batch`` / ``run_pca_batch`` put independent
instances on a leading vmap axis; on a single device that buys dispatch
efficiency, not parallelism.  The wrappers here place that instance axis on
a *mesh* axis instead (``shard_map``, docs/DESIGN.md §7): a wave of B
instances is split across the ``axis`` devices, each device runs the plain
vmapped solver on its local shard, and no collectives are needed because
instances never communicate.

Equality contract: instances are solved by exactly the per-instance
program regardless of which device hosts them, so

    run_psa_batch_sharded(...)[b] == run_psa_batch(...)[b]   (bitwise)

for every real instance b — verified in ``tests/test_batch_sharded.py``
on an emulated multi-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

The instance axis must divide evenly across the mesh axis, so waves are
padded up to a multiple of the axis size (``pad_to_mesh_multiple``):
dummy rows replicate instance 0 — a shape that is already compiling
anyway — and are dropped before returning.  Compiled programs are cached
per (solver, config, mesh, axis, arg-presence) so a long-lived service
reuses them across flushes, mirroring the power-of-two wave padding in
``serve.mapper``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import annealing, composite, genetic, mapping, qap
from .distributed import shard_map

Array = jax.Array

DEFAULT_AXIS = "instances"


def round_up_to_multiple(b: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``b``."""
    if m < 1:
        raise ValueError(f"multiple must be >= 1, got {m}")
    return -(-b // m) * m


def _replicate_row0(arr: Array, total: int) -> Array:
    pad = total - arr.shape[0]
    if pad == 0:
        return arr
    return jnp.concatenate(
        [arr, jnp.broadcast_to(arr[:1], (pad,) + arr.shape[1:])])


def pad_to_mesh_multiple(Cs: Array, Ms: Array, keys: Array,
                         n_valid: Optional[Array],
                         init_perm: Optional[Array], multiple: int
                         ) -> Tuple[Array, Array, Array, Optional[Array],
                                    Optional[Array], int]:
    """Pad the leading instance axis up to a multiple of the mesh axis size.

    Dummy rows replicate instance 0 (including its key / n_valid /
    warm-start row), so the padded wave only re-solves work that is being
    solved anyway and every row stays a well-formed instance.  Returns the
    padded arrays plus the original batch size B; callers slice ``[:B]``
    off the solver outputs.
    """
    B = Cs.shape[0]
    if B == 0:
        raise ValueError("empty instance batch")
    Bp = round_up_to_multiple(B, multiple)
    if Bp == B:
        return Cs, Ms, keys, n_valid, init_perm, B
    Cs = _replicate_row0(jnp.asarray(Cs), Bp)
    Ms = _replicate_row0(jnp.asarray(Ms), Bp)
    keys = _replicate_row0(jnp.asarray(keys), Bp)
    if n_valid is not None:
        n_valid = _replicate_row0(jnp.asarray(n_valid), Bp)
    if init_perm is not None:
        init_perm = _replicate_row0(jnp.asarray(init_perm), Bp)
    return Cs, Ms, keys, n_valid, init_perm, B


@functools.lru_cache(maxsize=None)
def _sharded_program(kind: str, cfg, num_processes: int, exchange: bool,
                     mesh: Mesh, axis: str, has_nv: bool, has_ip: bool):
    """Build (once per signature) the jitted shard_map program: each device
    runs the plain instance-vmapped solver on its local slice of the wave."""
    if kind == "psa":
        def impl(c, m, k, nv, ip):
            return annealing._psa_impl(c, m, k, cfg, num_processes,
                                       exchange, nv, ip)
    elif kind == "pga":
        def impl(c, m, k, nv, ip):
            return genetic._pga_impl(c, m, k, cfg, num_processes, nv, ip)
    elif kind == "pca":
        def impl(c, m, k, nv, ip):
            return composite._pca_impl(c, m, k, cfg, num_processes, nv, ip)
    else:
        raise ValueError(f"unknown solver kind {kind!r}")

    def local(*args):
        c, m, k = args[:3]
        nv = args[3] if has_nv else None
        ip = args[3 + has_nv] if has_ip else None
        return qap.vmap_instances(impl, c, m, k, nv, ip)

    spec = P(axis)
    nargs = 3 + has_nv + has_ip
    fn = shard_map(local, mesh=mesh, in_specs=(spec,) * nargs,
                   out_specs=(spec, spec, spec))
    return jax.jit(fn)


def _dispatch_sharded(kind: str, cfg, num_processes: int, exchange: bool,
                      Cs: Array, Ms: Array, keys: Array,
                      n_valid: Optional[Array], init_perm: Optional[Array],
                      mesh: Mesh, axis: str
                      ) -> Tuple[Array, Array, Array]:
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {axis!r}; axes: {tuple(mesh.shape)}")
    nshard = int(mesh.shape[axis])
    Cs, Ms, keys, n_valid, init_perm, B = pad_to_mesh_multiple(
        Cs, Ms, keys, n_valid, init_perm, nshard)
    fn = _sharded_program(kind, cfg, num_processes, exchange, mesh, axis,
                          n_valid is not None, init_perm is not None)
    args = [jnp.asarray(Cs), jnp.asarray(Ms), jnp.asarray(keys)]
    if n_valid is not None:
        args.append(jnp.asarray(n_valid))
    if init_perm is not None:
        args.append(jnp.asarray(init_perm))
    ps, fs, hist = fn(*args)
    return ps[:B], fs[:B], hist[:B]


def run_psa_batch_sharded(Cs: Array, Ms: Array, keys: Array,
                          cfg: annealing.SAConfig, num_processes: int = 4,
                          exchange: bool = True,
                          n_valid: Optional[Array] = None,
                          init_perm: Optional[Array] = None, *,
                          mesh: Mesh, axis: str = DEFAULT_AXIS
                          ) -> Tuple[Array, Array, Array]:
    """``annealing.run_psa_batch`` with the instance axis sharded over
    ``mesh.shape[axis]`` devices.  Same arguments and return values as the
    unsharded entry point (plus ``mesh``/``axis``); entry b is bitwise
    equal to the unsharded solve of instance b.
    """
    return _dispatch_sharded("psa", cfg, num_processes, exchange,
                             Cs, Ms, keys, n_valid, init_perm, mesh, axis)


def run_pga_batch_sharded(Cs: Array, Ms: Array, keys: Array,
                          cfg: genetic.GAConfig, num_processes: int = 4,
                          n_valid: Optional[Array] = None,
                          init_perm: Optional[Array] = None, *,
                          mesh: Mesh, axis: str = DEFAULT_AXIS
                          ) -> Tuple[Array, Array, Array]:
    """``genetic.run_pga_batch`` with the instance axis sharded over a mesh
    axis (see :func:`run_psa_batch_sharded` for the contract)."""
    return _dispatch_sharded("pga", cfg, num_processes, True,
                             Cs, Ms, keys, n_valid, init_perm, mesh, axis)


def run_pca_batch_sharded(Cs: Array, Ms: Array, keys: Array,
                          cfg: composite.CompositeConfig,
                          num_processes: int = 4,
                          n_valid: Optional[Array] = None,
                          init_perm: Optional[Array] = None, *,
                          mesh: Mesh, axis: str = DEFAULT_AXIS
                          ) -> Tuple[Array, Array, Array]:
    """``composite.run_pca_batch`` with the instance axis sharded over a
    mesh axis (see :func:`run_psa_batch_sharded` for the contract)."""
    return _dispatch_sharded("pca", cfg, num_processes, True,
                             Cs, Ms, keys, n_valid, init_perm, mesh, axis)


@functools.lru_cache(maxsize=None)
def _sharded_polish(rounds: int, mesh: Mesh, axis: str):
    """Jitted shard_map of ``mapping.polish_batch``: each device polishes
    its local slice of the wave.  Polishing the solvers' sharded output
    with the plain jitted polish would ask XLA to partition the Pallas
    delta kernel inside it, which it cannot do."""
    spec = P(axis)
    return jax.jit(shard_map(
        lambda c, m, p, k, nv: mapping.polish_batch(c, m, p, k, rounds, nv),
        mesh=mesh, in_specs=(spec,) * 5, out_specs=(spec, spec)))


def polish_batch_sharded(Cs: Array, Ms: Array, ps: Array, keys: Array,
                         rounds: int, n_valid: Array, *, mesh: Mesh,
                         axis: str = DEFAULT_AXIS) -> Tuple[Array, Array]:
    """``mapping.polish_batch`` with the instance axis sharded over
    ``mesh.shape[axis]`` devices (see :func:`run_psa_batch_sharded` for
    the contract); entry b is bitwise equal to the unsharded polish."""
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {axis!r}; axes: {tuple(mesh.shape)}")
    Cs, Ms, keys, n_valid, ps, B = pad_to_mesh_multiple(
        Cs, Ms, keys, n_valid, ps, int(mesh.shape[axis]))
    p, f = _sharded_polish(rounds, mesh, axis)(
        jnp.asarray(Cs), jnp.asarray(Ms), jnp.asarray(ps),
        jnp.asarray(keys), jnp.asarray(n_valid))
    return p[:B], f[:B]
