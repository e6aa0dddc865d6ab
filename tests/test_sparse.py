"""Sparse flows + multilevel pipeline: representation round-trips, bitwise
dispatch equality against the dense golden path, known-optimum torus
fixtures, the never-worse-than-coarse refinement guarantee, and the
engine's large-order routing (docs/DESIGN.md §10)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # optional test dependency
    from _hypothesis_compat import given, settings, st

from dataclasses import replace

from repro.core import (annealing, exact, genetic, mapping, multilevel,
                        qap, sparse)
from repro.kernels import ops, ref
from repro.serve.mapper import MappingEngine
from _fixtures import SA_SMALL, GA_SMALL, instance

SA_SPARSE = replace(SA_SMALL, flows="sparse")
GA_SPARSE = replace(GA_SMALL, flows="sparse")

# Tiny multilevel budget: one coarsening level on the n=16 torus fixture,
# small enough that the whole pipeline compiles + runs in seconds.
ML_TINY = multilevel.MultilevelConfig(
    coarse_n=8,
    coarse_sa=replace(SA_SMALL, solvers=2),
    refine_sa=replace(SA_SPARSE, solvers=2),
    final_polish_rounds=8)


def _sparse_instance(n, seed, density=0.2):
    """Integer-valued sparse (C, M): bitwise-exact f32 arithmetic."""
    rng = np.random.default_rng(seed)
    C, M = instance(n, seed)
    C = np.where(rng.random((n, n)) < density, C, 0.0).astype(np.float32)
    np.fill_diagonal(C, 0)
    return C, M


# ------------------------------------------------------------ representation
@pytest.mark.parametrize("n,density", [(8, 0.0), (12, 0.3), (24, 1.0)])
def test_sparse_round_trips_dense(n, density):
    C, _ = _sparse_instance(n, n, density)
    S = sparse.from_dense(C)
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(S)), C)
    assert S.n == n and S.nnz() == int((C != 0).sum())
    assert S.shape == (n, n)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 32),
       st.floats(0.0, 1.0))
def test_sparse_round_trip_property(seed, n, density):
    rng = np.random.default_rng(seed)
    C = np.where(rng.random((n, n)) < density,
                 rng.integers(1, 50, (n, n)), 0).astype(np.float32)
    np.fill_diagonal(C, 0)
    S = sparse.from_dense(C)
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(S)), C)


def test_from_dense_width_validation():
    C, _ = _sparse_instance(10, 0, 0.5)
    deg = int(sparse.max_degree(C))
    with pytest.raises(ValueError):
        sparse.from_dense(C, width=deg - 1)
    S = sparse.from_dense(C, width=deg + 3)   # extra padding is harmless
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(S)), C)


def test_from_dense_leading_batch():
    Cs = np.stack([_sparse_instance(12, s, 0.3)[0] for s in range(3)])
    S = sparse.from_dense(Cs)
    assert S.shape == (3, 12, 12)
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(S)), Cs)


def _asymmetric(n, seed, density):
    """Integer flows, asymmetric, with a nonzero diagonal and one empty
    row and column."""
    rng = np.random.default_rng(seed)
    C = ((rng.random((n, n)) < density)
         * rng.integers(1, 10, (n, n))).astype(np.float32)
    C[n // 2, :] = 0
    C[:, n // 3] = 0
    return C


@pytest.mark.parametrize("n,order,density,symmetric", [
    (16, 16, 0.3, True), (40, 64, 0.2, False), (97, 128, 0.1, True),
    (130, 256, 0.05, False), (12, 16, 0.0, True), (33, 64, 1.0, False)])
def test_from_nonzeros_matches_from_dense(n, order, density, symmetric):
    """The ELL blocks built from the nonzeros hold from_dense's stored
    entries of the padded matrix, in its order, with equal degrees;
    padding slots hold value 0 and a valid column."""
    C = _asymmetric(n, n, density)
    if symmetric:
        C = np.triu(C, 1) + np.triu(C, 1).T
    flows = sparse.nonzeros(C)
    width = max(sparse.max_degree(C), 4)
    got = sparse.from_nonzeros(flows, order, width)
    want = sparse.from_dense(np.pad(C, ((0, order - n),) * 2), width)
    assert got.shape == want.shape == (order, order)
    for cols, vals, deg, w_cols, w_vals, w_deg in (
            (got.cols, got.vals, got.deg, want.cols, want.vals, want.deg),
            (got.cols_t, got.vals_t, got.deg_t, want.cols_t, want.vals_t,
             want.deg_t)):
        np.testing.assert_array_equal(deg, w_deg)
        assert cols.dtype == w_cols.dtype and vals.dtype == w_vals.dtype
        stored = np.arange(width)[None, :] < np.asarray(deg)[:, None]
        np.testing.assert_array_equal(np.asarray(cols)[stored],
                                      np.asarray(w_cols)[stored])
        np.testing.assert_array_equal(np.asarray(vals)[stored],
                                      np.asarray(w_vals)[stored])
        assert (np.asarray(vals)[~stored] == 0).all()
        assert ((np.asarray(cols) >= 0) & (np.asarray(cols) < order)).all()
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(got)),
                                  np.pad(C, ((0, order - n),) * 2))
    if flows.nnz:
        with pytest.raises(ValueError):
            sparse.from_nonzeros(flows, order, sparse.max_degree(C) - 1)


def test_coalesce_sums_like_a_dense_accumulation():
    """Duplicates summed in float64 in their given order, rounded to
    float32; sums of zero and nothing else dropped; row-major."""
    rng = np.random.default_rng(3)
    n, k = 37, 4000
    rows, cols = rng.integers(0, n, k), rng.integers(0, n, k)
    vals = rng.normal(size=k) * rng.integers(0, 2, k)
    rows[(rows == 5) & (cols == 7)] = 6
    rows[:20], cols[:20] = 5, 7                     # these cancel to 0
    vals[:10], vals[10:20] = 0.5, -0.5
    acc = np.zeros((n, n))
    np.add.at(acc, (rows, cols), vals)
    want = acc.astype(np.float32)
    got = sparse.coalesce(n, rows, cols, vals)
    np.testing.assert_array_equal(got.dense(), want)
    assert got.nnz == np.count_nonzero(want) and want[5, 7] == 0
    flat = got.rows.astype(np.int64) * n + got.cols
    assert (np.diff(flat) > 0).all()


def test_mask_flows_sparse_matches_dense():
    C, _ = _sparse_instance(16, 1, 0.4)
    S = sparse.from_dense(C)
    for n_valid in (16, 9, 3):
        want = np.asarray(qap.mask_flows(jnp.asarray(C),
                                         jnp.asarray(n_valid, jnp.int32)))
        got = sparse.to_dense(qap.mask_flows(S, jnp.asarray(n_valid,
                                                            jnp.int32)))
        np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------- dispatch bitwise equality
def test_sparse_objective_bitwise_equals_dense():
    C, M = _sparse_instance(24, 2, 0.3)
    S = sparse.from_dense(C)
    C, M = jnp.asarray(C), jnp.asarray(M)
    perms = qap.random_permutations(jax.random.PRNGKey(0), 7, 24)
    np.testing.assert_array_equal(
        np.asarray(ops.qap_objective_sparse(S, M, perms)),
        np.asarray(ref.qap_objective_ref(C, M, perms)))
    # generic entry points route on the representation
    np.testing.assert_array_equal(
        np.asarray(ops.qap_objective(S, M, perms)),
        np.asarray(ops.qap_objective(C, M, perms)))
    np.testing.assert_array_equal(
        np.asarray(qap.objective(S, M, perms[0])),
        np.asarray(qap.objective(C, M, perms[0])))


def test_sparse_delta_bitwise_equals_dense():
    C, M = _sparse_instance(24, 3, 0.3)
    S = sparse.from_dense(C)
    C, M = jnp.asarray(C), jnp.asarray(M)
    p = qap.random_permutations(jax.random.PRNGKey(1), 1, 24)[0]
    pairs = qap.random_swap_pairs(jax.random.PRNGKey(2), 40, 24)
    np.testing.assert_array_equal(
        np.asarray(ops.qap_delta_sparse(S, M, p, pairs)),
        np.asarray(ref.qap_delta_ref(C, M, p, pairs)))
    a, b = int(pairs[0, 0]), int(pairs[0, 1])
    np.testing.assert_array_equal(
        np.asarray(qap.swap_delta(S, M, p, a, b)),
        np.asarray(qap.swap_delta(C, M, p, a, b)))


def test_sparse_delta_matches_true_recompute():
    C, M = _sparse_instance(20, 4, 0.4)
    S = sparse.from_dense(C)
    M = jnp.asarray(M)
    p = qap.random_permutations(jax.random.PRNGKey(3), 1, 20)[0]
    pairs = qap.random_swap_pairs(jax.random.PRNGKey(4), 16, 20)
    got = np.asarray(ops.qap_delta_sparse(S, M, p, pairs))
    f0 = float(qap.objective(S, M, p))
    for i, (a, b) in enumerate(np.asarray(pairs)):
        f1 = float(qap.objective(S, M, qap.swap_positions(p, int(a), int(b))))
        np.testing.assert_array_equal(got[i], np.float32(f1 - f0))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(4, 24))
def test_sparse_objective_equals_dense_property(seed, n):
    rng = np.random.default_rng(seed)
    C, M = _sparse_instance(n, seed % 1000, rng.uniform(0.0, 0.6))
    S = sparse.from_dense(C)
    perms = qap.random_permutations(jax.random.PRNGKey(seed % 97), 4, n)
    np.testing.assert_array_equal(
        np.asarray(ops.qap_objective_sparse(S, jnp.asarray(M), perms)),
        np.asarray(ref.qap_objective_ref(jnp.asarray(C), jnp.asarray(M),
                                         perms)))


def test_sparse_dispatch_masked_padded_instance():
    """Padding rows/cols masked away: sparse objective on the masked
    representation equals the dense masked objective bitwise."""
    bucket, n = 24, 17
    C = np.zeros((bucket, bucket), np.float32)
    M = np.zeros((bucket, bucket), np.float32)
    Cn, Mn = _sparse_instance(n, 5, 0.4)
    C[:n, :n], M[:n, :n] = Cn, Mn
    nv = jnp.asarray(n, jnp.int32)
    Sm = qap.mask_flows(sparse.from_dense(C), nv)
    Cm = qap.mask_flows(jnp.asarray(C), nv)
    perms = qap.random_permutations(jax.random.PRNGKey(7), 5, bucket)
    np.testing.assert_array_equal(
        np.asarray(ops.qap_objective_sparse(Sm, jnp.asarray(M), perms)),
        np.asarray(ref.qap_objective_ref(Cm, jnp.asarray(M), perms)))


def test_sparse_dispatch_under_vmap_matches_flat():
    """The hot-loop pattern: sparse dispatches traced per chain under an
    outer vmap equal the explicit leading-batch dispatch bitwise."""
    C, M = _sparse_instance(16, 6, 0.4)
    S = sparse.from_dense(C)
    M = jnp.asarray(M)
    perms = qap.random_permutations(jax.random.PRNGKey(8), 12,
                                    16).reshape(4, 3, 16)
    per_chain = jax.jit(jax.vmap(lambda p: ops.qap_objective_sparse(S, M, p)))
    flat = jax.jit(lambda: ops.qap_objective_sparse(S, M, perms))
    assert np.asarray(per_chain(perms)).tobytes() == \
        np.asarray(flat()).tobytes()


# --------------------------------------------------- solver path equivalence
def test_run_psa_sparse_bitwise_equals_dense():
    C, M = _sparse_instance(16, 10, 0.4)
    S = sparse.from_dense(C)
    key = jax.random.PRNGKey(0)
    pd, fd, hd = annealing.run_psa(jnp.asarray(C), jnp.asarray(M), key,
                                   SA_SMALL, 2)
    ps_, fs_, hs_ = annealing.run_psa(S, jnp.asarray(M), key, SA_SPARSE, 2)
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps_))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(fs_))
    np.testing.assert_array_equal(np.asarray(hd), np.asarray(hs_))


def test_run_psa_sparse_scan_loop_bitwise_equals_dense():
    """The scan-loop realisation goes through the same sparse dispatches."""
    C, M = _sparse_instance(16, 11, 0.4)
    S = sparse.from_dense(C)
    key = jax.random.PRNGKey(1)
    cfg_d = replace(SA_SMALL, loop="scan")
    cfg_s = replace(SA_SPARSE, loop="scan")
    pd, fd, _ = annealing.run_psa(jnp.asarray(C), jnp.asarray(M), key,
                                  cfg_d, 2)
    ps_, fs_, _ = annealing.run_psa(S, jnp.asarray(M), key, cfg_s, 2)
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps_))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(fs_))


def test_run_pga_sparse_bitwise_equals_dense():
    C, M = _sparse_instance(16, 12, 0.4)
    S = sparse.from_dense(C)
    key = jax.random.PRNGKey(2)
    pd, fd, hd = genetic.run_pga(jnp.asarray(C), jnp.asarray(M), key,
                                 GA_SMALL, 2)
    ps_, fs_, hs_ = genetic.run_pga(S, jnp.asarray(M), key, GA_SPARSE, 2)
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps_))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(fs_))
    np.testing.assert_array_equal(np.asarray(hd), np.asarray(hs_))


def test_run_psa_batch_sparse_masked_warm_bitwise_equals_dense():
    """Instance-batched sparse solve with padding masks and warm starts:
    bitwise-equal to the dense batched path."""
    bucket, sizes = 16, (12, 16, 9)
    B = len(sizes)
    Cs = np.zeros((B, bucket, bucket), np.float32)
    Ms = np.zeros((B, bucket, bucket), np.float32)
    for i, n in enumerate(sizes):
        Cn, Mn = _sparse_instance(n, 20 + i, 0.4)
        Cs[i, :n, :n], Ms[i, :n, :n] = Cn, Mn
    keys = jnp.stack([jax.random.PRNGKey(30 + i) for i in range(B)])
    nvs = jnp.asarray(sizes, jnp.int32)
    warm = jnp.stack([jnp.arange(bucket, dtype=jnp.int32)] * B)
    S = sparse.from_dense(Cs)
    pd, fd, _ = annealing.run_psa_batch(jnp.asarray(Cs), jnp.asarray(Ms),
                                        keys, SA_SMALL, 2, n_valid=nvs,
                                        init_perm=warm)
    ps_, fs_, _ = annealing.run_psa_batch(S, jnp.asarray(Ms), keys,
                                          SA_SPARSE, 2, n_valid=nvs,
                                          init_perm=warm)
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps_))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(fs_))


def test_run_psa_sparse_warm_start_never_worse():
    """init_perm chains survive into the result: the refined objective can
    never exceed the seed's (the guarantee multilevel rests on)."""
    inst = exact.make_torus((4, 4))
    S = sparse.from_dense(inst.C)
    M = jnp.asarray(inst.M)
    seed_p = jnp.asarray(inst.opt_perm, jnp.int32)      # already optimal
    _, f, _ = annealing.run_psa(S, M, jax.random.PRNGKey(3),
                                replace(SA_SPARSE, solvers=2), 2,
                                init_perm=seed_p)
    assert float(f) <= inst.optimum + 1e-6


def test_sparse_config_requires_sparse_flows():
    C, M = _sparse_instance(12, 13, 0.4)
    with pytest.raises(TypeError):
        annealing.run_psa(jnp.asarray(C), jnp.asarray(M),
                          jax.random.PRNGKey(0), SA_SPARSE, 2)
    with pytest.raises(TypeError):
        genetic.run_pga(jnp.asarray(C), jnp.asarray(M),
                        jax.random.PRNGKey(0), GA_SPARSE, 2)


def test_polish_sparse_bitwise_equals_dense():
    C, M = _sparse_instance(16, 14, 0.4)
    S = sparse.from_dense(C)
    p0 = qap.random_permutations(jax.random.PRNGKey(4), 1, 16)[0]
    key = jax.random.PRNGKey(5)
    pd, fd = mapping.polish(jnp.asarray(C), jnp.asarray(M), p0, key,
                            rounds=12)
    ps_, fs_ = mapping.polish(S, jnp.asarray(M), p0, key, rounds=12)
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps_))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(fs_))


# ----------------------------------------------------------- is_permutation
def test_is_permutation_correctness():
    n = 9
    good = jnp.asarray(np.random.default_rng(0).permutation(n), jnp.int32)
    assert bool(qap.is_permutation(good))
    dup = good.at[3].set(good[4])
    assert not bool(qap.is_permutation(dup))
    oob = good.at[0].set(n)
    assert not bool(qap.is_permutation(oob))
    neg = good.at[0].set(-1)
    assert not bool(qap.is_permutation(neg))


def test_is_permutation_batched_shapes():
    rng = np.random.default_rng(1)
    batch = np.stack([rng.permutation(7) for _ in range(6)]).astype(np.int32)
    batch[2, 0] = batch[2, 1]                   # one bad row
    got = np.asarray(qap.is_permutation(jnp.asarray(batch)))
    np.testing.assert_array_equal(got, [True, True, False, True, True, True])
    got3 = np.asarray(qap.is_permutation(jnp.asarray(batch.reshape(2, 3, 7))))
    np.testing.assert_array_equal(got3, got.reshape(2, 3))


def test_is_permutation_no_quadratic_intermediate():
    """Regression: the old one_hot realisation materialised an (n, n)
    float matrix per row.  Trace-level pin: no intermediate may reach
    n*n elements."""
    n = 4096
    p = jnp.arange(n, dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(qap.is_permutation)(p)
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.outvars:
            assert int(np.prod(v.aval.shape or (1,))) < n * n, \
                f"quadratic intermediate {v.aval} in {eqn.primitive.name}"
    assert bool(qap.is_permutation(p))


# -------------------------------------------------- known-optimum fixtures
def test_make_ring_optimum_matches_brute_force():
    inst = exact.make_ring(8)
    f_bf, _ = exact.brute_force(inst.C, inst.M)
    assert f_bf == pytest.approx(inst.optimum)
    f_opt = float(qap.objective(jnp.asarray(inst.C), jnp.asarray(inst.M),
                                jnp.asarray(inst.opt_perm)))
    assert f_opt == pytest.approx(inst.optimum)


def test_make_torus_optimum_matches_brute_force():
    inst = exact.make_torus((2, 4))
    f_bf, _ = exact.brute_force(inst.C, inst.M)
    assert f_bf == pytest.approx(inst.optimum)


@pytest.mark.parametrize("dims", [(4, 4), (2, 3, 4), (16,)])
def test_make_torus_optimum_attained_and_unbeaten(dims):
    inst = exact.make_torus(dims)
    C, M = jnp.asarray(inst.C), jnp.asarray(inst.M)
    n = C.shape[0]
    f_opt = float(qap.objective(C, M, jnp.asarray(inst.opt_perm)))
    assert f_opt == pytest.approx(inst.optimum)
    assert inst.optimum == pytest.approx(float(inst.C.sum()))
    perms = qap.random_permutations(jax.random.PRNGKey(n), 64, n)
    fs = np.asarray(qap.objective(C, M, perms))
    assert (fs >= inst.optimum - 1e-3).all()
    # sparse path agrees on the fixture bitwise
    S = sparse.from_dense(inst.C)
    np.testing.assert_array_equal(
        np.asarray(ops.qap_objective_sparse(S, M, perms)), fs)


# ------------------------------------------------------------- multilevel
def test_heavy_edge_matching_is_perfect_partition():
    C, _ = _sparse_instance(14, 40, 0.3)
    pairs = multilevel.heavy_edge_matching(C)
    assert pairs.shape == (7, 2)
    assert sorted(pairs.ravel().tolist()) == list(range(14))
    with pytest.raises(ValueError):
        multilevel.heavy_edge_matching(np.zeros((5, 5), np.float32))


def test_closest_pair_matching_is_perfect_partition():
    _, M = _sparse_instance(12, 41, 0.3)
    pairs = multilevel.closest_pair_matching(M)
    assert sorted(pairs.ravel().tolist()) == list(range(12))


def test_prolong_perm_is_permutation():
    rng = np.random.default_rng(42)
    nc = 6
    fp = rng.permutation(2 * nc).reshape(nc, 2)
    sp = rng.permutation(2 * nc).reshape(nc, 2)
    pc = rng.permutation(nc)
    p = multilevel.prolong_perm(pc, fp, sp)
    assert sorted(p.tolist()) == list(range(2 * nc))


def test_multilevel_never_worse_than_coarse():
    inst = exact.make_torus((4, 4))
    res = multilevel.solve_multilevel(inst.C, inst.M,
                                      jax.random.PRNGKey(0), ML_TINY)
    assert len(res.levels) == 1               # 16 -> 8, one level
    for lv in res.levels:
        assert lv.f_refined <= lv.f_prolonged + 1e-6
    # final polish never regresses the finest refinement
    assert res.objective <= res.levels[-1].f_refined + 1e-6
    assert res.objective >= inst.optimum - 1e-3
    p = np.asarray(res.perm)
    assert sorted(p.tolist()) == list(range(16))


def test_multilevel_odd_order_skips_coarsening():
    """An odd order no longer skips coarsening: one process is pinned on
    one node and the rest halves, 9 -> 4."""
    C, M = instance(9, 50)
    res = multilevel.solve_multilevel(C, M, jax.random.PRNGKey(1),
                                      replace(ML_TINY, coarse_n=4))
    assert [lv.n for lv in res.levels] == [9]     # odd order: coarsened
    assert res.levels[0].f_refined <= res.levels[0].f_prolonged
    assert sorted(np.asarray(res.perm).tolist()) == list(range(9))


@pytest.mark.slow
def test_multilevel_large_order_end_to_end():
    """n=1024 end-to-end through coarsen -> solve -> refine at a tiny
    budget: the level trace spans 1024 down to <= 64, every refinement
    is never-worse, and the result lands under the random-placement
    baseline on the known-optimum torus."""
    inst = exact.make_torus((32, 32))
    cfg = multilevel.MultilevelConfig(
        coarse_n=64,
        coarse_sa=replace(SA_SMALL, solvers=2),
        refine_sa=annealing.SAConfig(max_neighbors=4, iters_per_exchange=2,
                                     num_exchanges=2, solvers=2,
                                     flows="sparse"),
        final_polish_rounds=4)
    res = multilevel.solve_multilevel(inst.C, inst.M,
                                      jax.random.PRNGKey(2), cfg)
    assert [lv.n for lv in res.levels] == [128, 256, 512, 1024]
    for lv in res.levels:
        assert lv.f_refined <= lv.f_prolonged + 1e-6
    p = np.asarray(res.perm)
    assert sorted(p.tolist()) == list(range(1024))
    rng = np.random.default_rng(0)
    f_rand = min(
        float((inst.C.astype(np.float64)
               * inst.M.astype(np.float64)[np.ix_(q, q)]).sum())
        for q in (rng.permutation(1024) for _ in range(4)))
    assert res.objective < f_rand


# ------------------------------------------------------------ engine routing
def test_engine_large_bucket_routing():
    eng = MappingEngine(buckets=(8,), large_buckets=(32, 64),
                        multilevel_min_n=16, num_processes=2,
                        sa_cfg=SA_SMALL, multilevel_cfg=ML_TINY)
    assert eng.bucket_for(6) == 8
    assert eng.bucket_for(12) is None
    assert eng.large_bucket_for(12) is None       # below multilevel_min_n
    assert eng._route(12) is None
    assert eng.large_bucket_for(16) == 32
    assert eng.large_bucket_for(40) == 64
    assert eng.large_bucket_for(100) == 64        # largest label catches all
    assert eng._route(100) == 64


def test_engine_dense_buckets_win_collisions():
    eng = MappingEngine(buckets=(8, 32), large_buckets=(32, 64),
                        multilevel_min_n=16, num_processes=2,
                        sa_cfg=SA_SMALL)
    assert eng.bucket_for(20) == 32               # dense path keeps 32
    assert eng.large_bucket_for(40) == 64


def test_engine_multilevel_solve_and_cache():
    inst = exact.make_torus((4, 4))
    eng = MappingEngine(buckets=(8,), large_buckets=(16,),
                        multilevel_min_n=16, num_processes=2,
                        sa_cfg=SA_SMALL, multilevel_cfg=ML_TINY)
    r = eng.map_one(inst.C, inst.M, seed=1, cache_seed=True)
    assert r.bucket == 16 and not r.cached
    p = np.asarray(r.perm)
    assert sorted(p.tolist()) == list(range(16))
    f = float((inst.C.astype(np.float64)
               * inst.M.astype(np.float64)[np.ix_(p, p)]).sum())
    assert r.objective == pytest.approx(f)
    # the solve's identity F over the nonzeros is the dense sum exactly
    assert r.baseline == float((inst.C.astype(np.float64)
                                * inst.M.astype(np.float64)).sum())
    r2 = eng.map_one(inst.C, inst.M, seed=1, cache_seed=True)
    assert r2.cached and r2.baseline == r.baseline
    np.testing.assert_array_equal(np.asarray(r2.perm), p)


def test_engine_digest_tags_multilevel_route():
    from repro.serve.mapper import MapRequest
    inst = exact.make_torus((4, 4))
    kw = dict(buckets=(8,), large_buckets=(16,), multilevel_min_n=16,
              num_processes=2, sa_cfg=SA_SMALL)
    eng_a = MappingEngine(multilevel_cfg=ML_TINY, **kw)
    eng_b = MappingEngine(
        multilevel_cfg=replace(ML_TINY, final_polish_rounds=2), **kw)
    req = MapRequest(job_id="j", C=inst.C, M=inst.M, algorithm="psa", seed=0)
    assert eng_a.digest(req) != eng_b.digest(req)   # cfg is in the key
    small = MapRequest(job_id="k", C=inst.C[:8, :8], M=inst.M[:8, :8],
                       algorithm="psa", seed=0)
    assert eng_a.digest(small) == eng_b.digest(small)   # dense route: no tag


def test_engine_takes_multilevel_settings_as_a_mapping():
    from repro.serve.mapper import MapRequest
    inst = exact.make_torus((4, 4))
    kw = dict(buckets=(8,), large_buckets=(16,), multilevel_min_n=16,
              num_processes=2, sa_cfg=SA_SMALL)
    fields = {"coarse_n": ML_TINY.coarse_n, "coarse_sa": ML_TINY.coarse_sa,
              "refine_sa": ML_TINY.refine_sa,
              "final_polish_rounds": ML_TINY.final_polish_rounds}
    eng_a = MappingEngine(multilevel_cfg=ML_TINY, **kw)
    eng_b = MappingEngine(multilevel_cfg=fields, **kw)
    assert eng_b.multilevel_cfg == ML_TINY
    req = MapRequest(job_id="j", C=inst.C, M=inst.M, algorithm="psa", seed=0)
    assert eng_a.digest(req) == eng_b.digest(req)
    with pytest.raises(TypeError):
        MappingEngine(multilevel_cfg={"coarse": 8}, **kw)
