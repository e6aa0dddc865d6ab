"""Compile the served Pallas kernels (and the solver programs that call
them) for a described TPU v5e, without a chip.

Interpret mode accepts blocks and gathers that Mosaic refuses; these
compiles are what catch that before any chip time is spent.  Nothing
runs here, so they say nothing about results or speed.  The topology is
described inside a fixture (never at import: only one process may load
the TPU library, and pytest-xdist workers import every test file), and
the persistent compilation cache is off around the compiles, since an
entry compiled for an absent chip cannot be read back.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (annealing, batch_sharded, composite, genetic,
                        mapping, sparse)
from repro.kernels import ops
from repro.kernels.qap_delta import (ROW_FORM_MAX_N, qap_delta_pallas_batch,
                                     qap_delta_rows_pallas_batch)
from repro.kernels.qap_objective import (MAX_KERNEL_N,
                                         qap_objective_pallas_batch)
from repro.kernels.qap_sparse import (MAX_SPARSE_KERNEL_N,
                                      qap_delta_sparse_pallas_batch,
                                      qap_objective_sparse_pallas_batch)
from repro.serve.mapper import MappingEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), (batch_sharded.DEFAULT_AXIS,))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flows(sharding, lead, n, d):
    i = lambda *s: _spec(sharding, lead + s, jnp.int32)
    f = lambda *s: _spec(sharding, lead + s)
    return sparse.SparseFlows(i(n, d), f(n, d), i(n, d), f(n, d), i(n),
                              i(n))


def _compile_has_kernel(fn, *args, **kwargs):
    text = jax.jit(fn, **kwargs).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


# Dense kernels at bucket 128 and at their cap; the instance-batched form
# at the cap is the one that needs the raised VMEM limit.
@pytest.mark.parametrize("n,lead", [(128, ()), (MAX_KERNEL_N, (4,))])
def test_objective_kernel_compiles(one_chip, n, lead):
    s = lambda *shape: _spec(one_chip, shape)
    _compile_has_kernel(qap_objective_pallas_batch, s(*lead, n, n),
                        s(*lead, n, n), _spec(one_chip, (4, 16, n),
                                              jnp.int32))


@pytest.mark.parametrize("n,lead", [(128, ()), (MAX_KERNEL_N, (4,))])
def test_delta_kernel_compiles(one_chip, n, lead):
    s = lambda *shape: _spec(one_chip, shape)
    _compile_has_kernel(qap_delta_pallas_batch, s(*lead, n, n),
                        s(*lead, n, n), _spec(one_chip, (64, n), jnp.int32),
                        _spec(one_chip, (64, 25, 2), jnp.int32))


# The row form at bucket 128 and at its cap, at the SA event loop's shapes
# (64 rows, 25 candidates) and the polish's (4 rows, 256 candidates: one
# full block), with shared and instance-batched matrices.
@pytest.mark.parametrize("n", [128, ROW_FORM_MAX_N])
@pytest.mark.parametrize("lead,rows,k", [((), 64, 25), ((4,), 64, 25),
                                         ((4,), 4, 256)])
def test_delta_row_form_compiles(one_chip, n, lead, rows, k):
    s = lambda *shape: _spec(one_chip, shape)
    text = jax.jit(qap_delta_rows_pallas_batch).lower(
        s(*lead, n, n), s(*lead, n, n), _spec(one_chip, (rows, n), jnp.int32),
        _spec(one_chip, (rows, k, 2), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "qap_delta" in text


# Sparse kernels at bucket 128 and at their cap; d=200 spans two
# 128-lane chunks of a sparse row.
@pytest.mark.parametrize("n,d,lead", [(128, 20, ()),
                                      (MAX_SPARSE_KERNEL_N, 200, (2,))])
def test_sparse_objective_kernel_compiles(one_chip, n, d, lead):
    _compile_has_kernel(qap_objective_sparse_pallas_batch,
                        _flows(one_chip, lead, n, d),
                        _spec(one_chip, lead + (n, n)),
                        _spec(one_chip, (2, 4, n), jnp.int32))


@pytest.mark.parametrize("n,d,lead", [(128, 20, ()),
                                      (MAX_SPARSE_KERNEL_N, 20, (2,))])
def test_sparse_delta_kernel_compiles(one_chip, n, d, lead):
    _compile_has_kernel(qap_delta_sparse_pallas_batch,
                        _flows(one_chip, lead, n, d),
                        _spec(one_chip, lead + (n, n)),
                        _spec(one_chip, (4, n), jnp.int32),
                        _spec(one_chip, (4, 256, 2), jnp.int32))


@pytest.mark.parametrize("algorithm", ["psa", "pga", "pca", "polish",
                                       "refine", "refine_padded",
                                       "ml_polish"])
def test_served_solver_programs_compile(one_chip, monkeypatch, algorithm):
    """The engine's bucket-128 solver programs (default budgets) and a
    multilevel sparse refinement level, traced down the TPU dispatch
    path: every XLA op and kernel in them must compile for v5e.  The
    padded ones are the multilevel programs of a level below its power of
    two (``n_valid``), with the event loop's counts: a refinement level at
    order 1024 and ELL width 768, and the final polish at 2048 and 384."""
    eng = MappingEngine()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    B, n = 4, 128
    mats = _spec(one_chip, (B, n, n))
    keys = _spec(one_chip, (B, 2), jnp.uint32)
    nv = _spec(one_chip, (B,), jnp.int32)
    cca = composite.CompositeConfig(sa=eng.sa_cfg, ga=eng.ga_cfg)
    programs = {
        "psa": lambda: annealing.run_psa_batch.lower(
            mats, mats, keys, eng.sa_cfg, 2, n_valid=nv),
        "pga": lambda: genetic.run_pga_batch.lower(
            mats, mats, keys, eng.ga_cfg, 2, n_valid=nv),
        "pca": lambda: composite.run_pca_batch.lower(
            mats, mats, keys, cca, 2, n_valid=nv),
        "polish": lambda: mapping.polish_batch.lower(
            mats, mats, _spec(one_chip, (B, n), jnp.int32), keys,
            eng.polish_rounds, nv),
        "refine": lambda: annealing.run_psa.lower(
            _flows(one_chip, (), 1024, 130), _spec(one_chip, (1024, 1024)),
            _spec(one_chip, (2,), jnp.uint32),
            eng.multilevel_cfg.refine_sa, 2,
            init_perm=_spec(one_chip, (1024,), jnp.int32)),
        "refine_padded": lambda: annealing.run_psa.lower(
            _flows(one_chip, (), 1024, 768), _spec(one_chip, (1024, 1024)),
            _spec(one_chip, (2,), jnp.uint32),
            eng.multilevel_cfg.refine_sa, 2,
            n_valid=_spec(one_chip, (), jnp.int32),
            init_perm=_spec(one_chip, (1024,), jnp.int32), counts=True),
        "ml_polish": lambda: mapping.polish_jit.lower(
            _flows(one_chip, (), 2048, 384), _spec(one_chip, (2048, 2048)),
            _spec(one_chip, (2048,), jnp.int32),
            _spec(one_chip, (2,), jnp.uint32),
            rounds=eng.multilevel_cfg.final_polish_rounds,
            n_valid=_spec(one_chip, (), jnp.int32)),
    }
    # Trace caches key on signatures only: drop TPU-path traces so they
    # never leak into (or come from) the CPU-path tests.
    jax.clear_caches()
    try:
        text = programs[algorithm]().compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text, f"{algorithm}: no Pallas kernel"


@pytest.mark.parametrize("algorithm", ["psa", "pga", "pca", "polish"])
def test_sharded_engine_programs_compile_for_four_chips(four_chips,
                                                        monkeypatch,
                                                        algorithm):
    """The mesh engine's bucket-128 programs, instance axis sharded over
    the four chips of a v5e:2x2: each device runs the kernels on its own
    quarter of the wave (a Pallas kernel cannot be partitioned by XLA, so
    every program that reaches one must be a shard_map)."""
    eng = MappingEngine(mesh=four_chips)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    B, n = 16, 128
    sh = NamedSharding(four_chips, P(batch_sharded.DEFAULT_AXIS))
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh)
    mats, keys = spec((B, n, n)), spec((B, 2), jnp.uint32)
    nv = spec((B,), jnp.int32)
    cfgs = {"psa": eng.sa_cfg, "pga": eng.ga_cfg,
            "pca": composite.CompositeConfig(sa=eng.sa_cfg, ga=eng.ga_cfg)}
    if algorithm == "polish":
        prog = batch_sharded._sharded_polish(eng.polish_rounds, four_chips,
                                             batch_sharded.DEFAULT_AXIS)
        args = (mats, mats, spec((B, n), jnp.int32), keys, nv)
    else:
        prog = batch_sharded._sharded_program(
            algorithm, cfgs[algorithm], eng.num_processes, True, four_chips,
            batch_sharded.DEFAULT_AXIS, True, False)
        args = (mats, mats, keys, nv)
    jax.clear_caches()
    try:
        compiled = prog.lower(*args).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    for out in compiled.output_shardings:
        assert out.spec == P(batch_sharded.DEFAULT_AXIS)
        assert len(out.device_set) == 4
