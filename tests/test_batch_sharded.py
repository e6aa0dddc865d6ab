"""Mesh-sharded batch dispatch: sharded == unsharded bitwise, padding to
mesh-multiple wave sizes, and the engine's mesh option.

The single-device tests run the real shard_map path on a 1-device mesh
(the code path is identical; only the axis size differs).  The genuinely
multi-device equality check runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` -- the flag must be
set before jax initialises, which the already-running test process cannot
do -- unless the current process *already* sees multiple devices (the CI
multi-device job), in which case it runs inline.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (annealing, batch_sharded, composite, genetic,
                        mapping)
from repro.launch.mesh import make_instance_mesh
from repro.serve.mapper import MapRequest, MappingEngine

from _fixtures import (SA_SMALL, GA_SMALL, PCA_SMALL,
                       instance as _instance, padded_batch as _padded_batch)


def _assert_bitwise(sharded, unsharded):
    sp, sf, sh = sharded
    up, uf, uh = unsharded
    assert np.asarray(sf).tobytes() == np.asarray(uf).tobytes()
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(up))
    np.testing.assert_array_equal(np.asarray(sh), np.asarray(uh))


# ------------------------------------------------- sharded == unsharded
def _equality_check(nshard):
    """Shared body: all three solvers, mixed n_valid, warm starts, and a
    wave size (5) that does not divide the mesh axis (forces padding)."""
    mesh = make_instance_mesh(nshard)
    sizes = [6, 8, 8, 5, 7]
    Cs, Ms, nvs, keys = _padded_batch(sizes, bucket=8)
    ips = np.full((len(sizes), 8), -1, np.int32)   # warm rows 0 and 3
    for i in (0, 3):
        n = sizes[i]
        ips[i, :n] = np.roll(np.arange(n), 1)
        ips[i, n:] = np.arange(n, 8)
    ips = jnp.asarray(ips)

    _assert_bitwise(
        batch_sharded.run_psa_batch_sharded(
            Cs, Ms, keys, SA_SMALL, 2, n_valid=nvs, init_perm=ips,
            mesh=mesh),
        annealing.run_psa_batch(Cs, Ms, keys, SA_SMALL, 2, n_valid=nvs,
                                init_perm=ips))
    _assert_bitwise(
        batch_sharded.run_pga_batch_sharded(
            Cs, Ms, keys, GA_SMALL, 2, n_valid=nvs, mesh=mesh),
        genetic.run_pga_batch(Cs, Ms, keys, GA_SMALL, 2, n_valid=nvs))
    _assert_bitwise(
        batch_sharded.run_pca_batch_sharded(
            Cs, Ms, keys, PCA_SMALL, 2, n_valid=nvs, mesh=mesh),
        composite.run_pca_batch(Cs, Ms, keys, PCA_SMALL, 2, n_valid=nvs))
    # the engine's final polish is sharded the same way
    ps = jnp.where(ips < 0, jnp.arange(8, dtype=jnp.int32), ips)
    sp, sf = batch_sharded.polish_batch_sharded(Cs, Ms, ps, keys, 6, nvs,
                                                mesh=mesh)
    up, uf = mapping.polish_batch(Cs, Ms, ps, keys, 6, nvs)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(up))
    assert np.asarray(sf).tobytes() == np.asarray(uf).tobytes()


def test_sharded_matches_unsharded_single_device():
    _equality_check(nshard=1)


@pytest.mark.slow
def test_sharded_matches_unsharded_multi_device():
    """Bitwise equality on a real multi-device instance mesh."""
    if jax.device_count() >= 4:
        _equality_check(nshard=4)       # CI multi-device job: run inline
        return
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src" + os.pathsep
                          + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, __file__, "--multi-device-check"],
        cwd=Path(__file__).resolve().parents[1], env=env,
        capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MULTI-DEVICE-OK" in proc.stdout, proc.stdout + proc.stderr


# ------------------------------------------------------ padding helpers
def test_round_up_to_multiple():
    assert batch_sharded.round_up_to_multiple(5, 4) == 8
    assert batch_sharded.round_up_to_multiple(8, 4) == 8
    assert batch_sharded.round_up_to_multiple(1, 4) == 4
    with pytest.raises(ValueError):
        batch_sharded.round_up_to_multiple(3, 0)


def test_pad_to_mesh_multiple_replicates_instance_zero():
    sizes = [6, 8, 5]
    Cs, Ms, nvs, keys = _padded_batch(sizes, bucket=8)
    ips = jnp.asarray(np.full((3, 8), -1, np.int32))
    pCs, pMs, pkeys, pnvs, pips, B = batch_sharded.pad_to_mesh_multiple(
        Cs, Ms, keys, nvs, ips, multiple=4)
    assert B == 3
    for arr in (pCs, pMs, pkeys, pnvs, pips):
        assert arr.shape[0] == 4
    np.testing.assert_array_equal(np.asarray(pCs[3]), np.asarray(Cs[0]))
    np.testing.assert_array_equal(np.asarray(pMs[3]), np.asarray(Ms[0]))
    np.testing.assert_array_equal(np.asarray(pkeys[3]), np.asarray(keys[0]))
    assert int(pnvs[3]) == sizes[0]
    np.testing.assert_array_equal(np.asarray(pips[3]), np.asarray(ips[0]))


def test_pad_to_mesh_multiple_noop_and_optional_args():
    Cs, Ms, nvs, keys = _padded_batch([8, 8], bucket=8)
    pCs, pMs, pkeys, pnvs, pips, B = batch_sharded.pad_to_mesh_multiple(
        Cs, Ms, keys, None, None, multiple=2)
    assert B == 2 and pCs is Cs and pnvs is None and pips is None
    with pytest.raises(ValueError):
        batch_sharded.pad_to_mesh_multiple(Cs[:0], Ms[:0], keys[:0],
                                           None, None, multiple=2)


def test_dispatch_rejects_unknown_axis():
    mesh = make_instance_mesh(1)
    Cs, Ms, nvs, keys = _padded_batch([8], bucket=8)
    with pytest.raises(ValueError, match="no axis"):
        batch_sharded.run_psa_batch_sharded(
            Cs, Ms, keys, SA_SMALL, 2, n_valid=nvs, mesh=mesh,
            axis="nope")


# ------------------------------------------------------- engine integration
def _engine_equality_check(nshard):
    """Same request stream through a meshed and an unmeshed engine must
    produce bitwise-identical permutations and objectives."""
    mesh = make_instance_mesh(nshard)
    reqs = []
    M_shared = _instance(8, 99)[1]
    for i in range(5):
        C, _ = _instance(6 + (i % 2) * 2, 40 + i)
        n = C.shape[0]
        reqs.append(MapRequest(job_id=f"j{i}", C=C,
                               M=M_shared[:n, :n], seed=i))
    out = {}
    for name, m in (("plain", None), ("mesh", mesh)):
        eng = MappingEngine(buckets=(8,), num_processes=2,
                            sa_cfg=SA_SMALL, polish_rounds=8, mesh=m)
        for r in reqs:
            eng.submit(r)
        out[name] = eng.flush()
    for jid in out["plain"]:
        a, b = out["plain"][jid], out["mesh"][jid]
        assert a.objective == b.objective
        np.testing.assert_array_equal(a.perm, b.perm)
        assert a.warm_start == b.warm_start


def test_engine_mesh_matches_unsharded_engine():
    _engine_equality_check(nshard=1)


def test_engine_rejects_mesh_without_axis():
    mesh = make_instance_mesh(1, axis="other")
    with pytest.raises(ValueError, match="no axis"):
        MappingEngine(mesh=mesh)


def test_placement_configure_engine_mesh():
    from repro.launch import placement
    placement.configure_engine_mesh(make_instance_mesh(1))
    try:
        eng = placement.get_engine()
        assert eng.mesh is not None
        C, M = _instance(6, 3)
        res = placement.solve_placement(C, M)
        assert res.cost_after <= res.cost_before
    finally:
        placement.reset_default_service()
    assert placement.get_engine().mesh is None


if __name__ == "__main__":
    if "--multi-device-check" in sys.argv:
        assert jax.device_count() >= 4, \
            f"expected >=4 devices, got {jax.device_count()}"
        _equality_check(nshard=4)
        # engine-level too: meshed engine == plain engine, across devices
        _engine_equality_check(nshard=4)
        print("MULTI-DEVICE-OK")
