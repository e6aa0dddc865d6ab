"""Pallas kernel validation: interpret-mode vs pure-jnp oracle.

Sweeps shapes (all paper orders that fit the kernel cap) and dtypes, as
required for every kernel in the repo.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import mosaic, ref, ops
from repro.kernels.qap_objective import (MAX_KERNEL_N, qap_objective_pallas,
                                         qap_objective_pallas_batch)
from repro.kernels.qap_delta import (ROW_FORM_MAX_N, qap_delta_pallas,
                                     qap_delta_pallas_batch,
                                     qap_delta_rows_pallas_batch)
from repro.kernels.qap_sparse import (qap_delta_sparse_pallas_batch,
                                      qap_objective_sparse_pallas_batch)
from repro.core import qap, sparse


def _instance(rng, n, dtype):
    C = rng.integers(0, 50, (n, n)).astype(dtype)
    M = rng.integers(0, 20, (n, n)).astype(dtype)
    np.fill_diagonal(C, 0)
    np.fill_diagonal(M, 0)
    return jnp.asarray(C), jnp.asarray(M)


@pytest.mark.parametrize("n", [27, 45, 75, 125, 128, 175, 343])
@pytest.mark.parametrize("batch", [1, 8])
def test_objective_kernel_matches_ref(n, batch):
    rng = np.random.default_rng(n * 7 + batch)
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(n), batch, n)
    got = qap_objective_pallas(C, M, perms, interpret=True)
    want = ref.qap_objective_ref(C, M, perms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("n", [27, 125, 343])
@pytest.mark.parametrize("batch,p_cnt", [(1, 6), (3, 5), (4, 12)])
def test_objective_kernel_batch_matches_ref(n, batch, p_cnt):
    """Interpret-mode equality for the leading-batch objective kernel:
    perms (B, P, N) -> (B, P), one grid over every pair."""
    rng = np.random.default_rng(n + batch + p_cnt)
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(batch), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    got = qap_objective_pallas_batch(C, M, perms, interpret=True)
    want = ref.qap_objective_ref(C, M, perms)
    assert got.shape == (batch, p_cnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_objective_kernel_batch_matches_single_rows():
    """Each leading-batch row equals the lead-free kernel on that row."""
    rng = np.random.default_rng(3)
    n, batch, p_cnt = 45, 4, 7
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(1), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    got = np.asarray(qap_objective_pallas_batch(C, M, perms, interpret=True))
    for i in range(batch):
        row = np.asarray(qap_objective_pallas(C, M, perms[i], interpret=True))
        np.testing.assert_array_equal(got[i], row)


def test_objective_kernel_batch_instance_matrices():
    """C/M may carry the leading instance axis (the batched solvers'
    case): row b of perms evaluates against C[b], M[b]."""
    rng = np.random.default_rng(4)
    n, batch, p_cnt = 27, 3, 5
    Cs, Ms = zip(*[_instance(rng, n, np.float32) for _ in range(batch)])
    Cs, Ms = jnp.stack(Cs), jnp.stack(Ms)
    perms = qap.random_permutations(jax.random.PRNGKey(2), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    got = qap_objective_pallas_batch(Cs, Ms, perms, interpret=True)
    want = jnp.stack([ref.qap_objective_ref(Cs[b], Ms[b], perms[b])
                      for b in range(batch)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_delta_kernel_batch_instance_matrices():
    """Instance-batched C/M for the delta kernel: permutation rows
    r*B//B0 .. belong to instance r."""
    rng = np.random.default_rng(5)
    n, b0, rpt, k = 27, 3, 2, 8
    Cs, Ms = zip(*[_instance(rng, n, np.float32) for _ in range(b0)])
    Cs, Ms = jnp.stack(Cs), jnp.stack(Ms)
    ps = jnp.stack([jnp.asarray(rng.permutation(n).astype(np.int32))
                    for _ in range(b0 * rpt)])
    pairs = jnp.stack([qap.random_swap_pairs(jax.random.PRNGKey(i), k, n)
                       for i in range(b0 * rpt)])
    got = qap_delta_pallas_batch(Cs, Ms, ps, pairs, interpret=True)
    want = jnp.concatenate([
        ref.qap_delta_ref(Cs[r], Ms[r], ps[r * rpt:(r + 1) * rpt],
                          pairs[r * rpt:(r + 1) * rpt]) for r in range(b0)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_objective_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    n, batch = 75, 4
    C, M = _instance(rng, n, np.float32)
    C, M = C.astype(dtype), M.astype(dtype)
    got = qap_objective_pallas(C, M, qap.random_permutations(jax.random.PRNGKey(1), batch, n),
                               interpret=True)
    want = ref.qap_objective_ref(C, M, qap.random_permutations(jax.random.PRNGKey(1), batch, n))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("n", [27, 45, 75, 125, 128, 175, 343, 729])
@pytest.mark.parametrize("k", [1, 16, 125])
def test_delta_kernel_matches_ref(n, k):
    rng = np.random.default_rng(n + k)
    C, M = _instance(rng, n, np.float32)
    p = jnp.asarray(rng.permutation(n).astype(np.int32))
    pairs = qap.random_swap_pairs(jax.random.PRNGKey(k), k, n)
    got = qap_delta_pallas(C, M, p, pairs, interpret=True)
    want = ref.qap_delta_ref(C, M, p, pairs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


def test_delta_kernel_matches_true_recompute():
    """Kernel deltas equal full objective recomputation, not just the ref formula."""
    rng = np.random.default_rng(5)
    n = 45
    C, M = _instance(rng, n, np.float32)
    p = jnp.asarray(rng.permutation(n).astype(np.int32))
    pairs = qap.random_swap_pairs(jax.random.PRNGKey(2), 32, n)
    got = np.asarray(qap_delta_pallas(C, M, p, pairs, interpret=True))
    f0 = float(qap.objective(C, M, p))
    for i, (a, b) in enumerate(np.asarray(pairs)):
        f1 = float(qap.objective(C, M, qap.swap_positions(p, int(a), int(b))))
        np.testing.assert_allclose(got[i], f1 - f0, rtol=1e-4, atol=1e-3)


def test_ops_dispatch_cpu():
    """On CPU the wrappers route to the reference implementation."""
    rng = np.random.default_rng(1)
    n = 27
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(0), 3, n)
    np.testing.assert_allclose(np.asarray(ops.qap_objective(C, M, perms)),
                               np.asarray(ref.qap_objective_ref(C, M, perms)))
    p = perms[0]
    pairs = qap.random_swap_pairs(jax.random.PRNGKey(3), 8, n)
    np.testing.assert_allclose(np.asarray(ops.qap_delta(C, M, p, pairs)),
                               np.asarray(ref.qap_delta_ref(C, M, p, pairs)))


def _batched_candidates(rng, n, batch, k):
    ps = jnp.stack([jnp.asarray(rng.permutation(n).astype(np.int32))
                    for _ in range(batch)])
    pairs = jnp.stack([qap.random_swap_pairs(jax.random.PRNGKey(i), k, n)
                       for i in range(batch)])
    return ps, pairs


@pytest.mark.parametrize("n", [27, 125, 343])
@pytest.mark.parametrize("batch,k", [(1, 16), (6, 10), (4, 50)])
def test_delta_kernel_batch_matches_ref(n, batch, k):
    """Interpret-mode equality for the leading-batch Pallas delta kernel."""
    rng = np.random.default_rng(n + batch + k)
    C, M = _instance(rng, n, np.float32)
    ps, pairs = _batched_candidates(rng, n, batch, k)
    got = qap_delta_pallas_batch(C, M, ps, pairs, interpret=True)
    want = ref.qap_delta_ref(C, M, ps, pairs)
    assert got.shape == (batch, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_delta_kernel_batch_matches_single_rows():
    """Each batch row equals the single-permutation kernel on that row."""
    rng = np.random.default_rng(9)
    n, batch, k = 45, 5, 12
    C, M = _instance(rng, n, np.float32)
    ps, pairs = _batched_candidates(rng, n, batch, k)
    got = np.asarray(qap_delta_pallas_batch(C, M, ps, pairs, interpret=True))
    for i in range(batch):
        row = np.asarray(qap_delta_pallas(C, M, ps[i], pairs[i],
                                          interpret=True))
        np.testing.assert_array_equal(got[i], row)


def test_ops_delta_leading_batch_dispatch():
    """ops.qap_delta accepts (..., N)/(..., K, 2) leading batch dims: the
    CPU path is bitwise-equal per candidate to qap.swap_delta, and the
    forced-Pallas interpret path matches numerically."""
    rng = np.random.default_rng(2)
    n, batch, k = 27, 6, 10
    C, M = _instance(rng, n, np.float32)
    ps, pairs = _batched_candidates(rng, n, batch, k)

    got = ops.qap_delta(C, M, ps, pairs)
    assert got.shape == (batch, k)
    scalar = np.stack([
        [float(qap.swap_delta(C, M, ps[i], pairs[i, j, 0], pairs[i, j, 1]))
         for j in range(k)] for i in range(batch)])
    np.testing.assert_array_equal(np.asarray(got), scalar.astype(np.float32))

    # 3-D leading shape flattens to the same values
    got3 = ops.qap_delta(C, M, ps.reshape(2, 3, n),
                         pairs.reshape(2, 3, k, 2))
    np.testing.assert_array_equal(np.asarray(got3).reshape(batch, k),
                                  np.asarray(got))

    # forced Pallas (interpret) leading-batch path agrees with the ref
    gotp = ops.qap_delta(C, M, ps, pairs, force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(gotp), np.asarray(got),
                               rtol=1e-4, atol=1e-3)


def test_ops_delta_under_vmap_matches_flat_dispatch():
    """The hot-loop usage pattern: ops.qap_delta traced per chain under an
    outer vmap must equal the explicit leading-batch dispatch bitwise on
    the CPU path."""
    rng = np.random.default_rng(3)
    n, batch, k = 32, 8, 10
    C, M = _instance(rng, n, np.float32)
    ps, pairs = _batched_candidates(rng, n, batch, k)
    per_chain = jax.jit(jax.vmap(lambda p, pr: ops.qap_delta(C, M, p, pr)))
    flat = jax.jit(lambda: ops.qap_delta(C, M, ps, pairs))
    assert np.asarray(per_chain(ps, pairs)).tobytes() == \
        np.asarray(flat()).tobytes()


def _padded_rows(rng, n, sizes, rpt, k):
    """Instances of the given valid sizes zero-padded to order n (one per
    size), rpt permutations each with identity pad tails, and k candidate
    swaps per permutation inside its valid prefix."""
    Cs, Ms = np.zeros((2, len(sizes), n, n), np.float32)
    ps = np.tile(np.arange(n, dtype=np.int32), (len(sizes) * rpt, 1))
    pairs = np.zeros((len(sizes) * rpt, k, 2), np.int32)
    for r, nv in enumerate(sizes):
        C, M = _instance(rng, nv, np.float32)
        Cs[r, :nv, :nv], Ms[r, :nv, :nv] = C, M
        for i in range(r * rpt, (r + 1) * rpt):
            ps[i, :nv] = rng.permutation(nv)
            pairs[i] = [rng.choice(nv, 2, replace=False) for _ in range(k)]
    return (jnp.asarray(Cs), jnp.asarray(Ms), jnp.asarray(ps),
            jnp.asarray(pairs))


# (n, k, valid sizes, rows per instance, batched C/M): n_pad 128 and 256,
# one to 256 candidates (256 fills a row-form block), padded instances.
@pytest.mark.parametrize("n,k,sizes,rpt,batched", [
    (5, 3, (5,), 3, False),
    (5, 25, (5, 4), 2, True),
    (32, 1, (32, 20), 3, True),
    (32, 256, (32,), 2, False),
    (100, 25, (100, 61, 2), 2, True),
    (128, 3, (128,), 4, False),
    (128, 25, (128, 100, 60, 17), 2, True),
    (128, 256, (128, 90), 1, True),
    (200, 1, (200,), 2, False),
    (200, 25, (200, 150), 2, True),
    (200, 256, (199,), 2, False),
])
def test_delta_row_form_bitwise(n, k, sizes, rpt, batched):
    """The row form of the dense delta kernel equals the reference and
    the per-candidate form bit for bit on integer instances, with shared
    or instance-batched matrices and identity pad tails."""
    rng = np.random.default_rng(n * 31 + k)
    Cs, Ms, ps, pairs = _padded_rows(rng, n, sizes, rpt, k)
    if batched:
        want = jnp.concatenate([
            ref.qap_delta_ref(Cs[r], Ms[r], ps[r * rpt:(r + 1) * rpt],
                              pairs[r * rpt:(r + 1) * rpt])
            for r in range(len(sizes))])
    else:
        Cs, Ms = Cs[0], Ms[0]
        want = ref.qap_delta_ref(Cs, Ms, ps, pairs)
    rows = qap_delta_rows_pallas_batch(Cs, Ms, ps, pairs, interpret=True)
    cands = qap_delta_pallas_batch(Cs, Ms, ps, pairs, interpret=True)
    assert rows.shape == (len(ps), k)
    assert np.asarray(rows).tobytes() == np.asarray(want).tobytes()
    assert np.asarray(rows).tobytes() == np.asarray(cands).tobytes()


@pytest.mark.parametrize("scale", [1.0, 1e-20, 1e20])
def test_split_onehot_dots_gather_exactly(scale):
    """The three-part bfloat16 one-hot dots rebuild every gathered float32
    value exactly, whatever its significand and exponent."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((128, 128)) * scale, jnp.float32)
    idx = rng.integers(0, 128, 128).astype(np.int32)
    oh = mosaic.onehot(jnp.asarray(idx)[None], 128)      # oh[j, k] = idx_k == j
    np.testing.assert_array_equal(np.asarray(mosaic.dot_onehot(x, oh)),
                                  np.asarray(x)[:, idx])
    np.testing.assert_array_equal(np.asarray(mosaic.onehot_dot(oh.T, x)),
                                  np.asarray(x)[idx, :])


@pytest.mark.parametrize("n,form", [(5, "row"), (ROW_FORM_MAX_N, "row"),
                                    (ROW_FORM_MAX_N + 1, "candidate"),
                                    (MAX_KERNEL_N, "candidate"),
                                    (MAX_KERNEL_N + 1, "reference")])
def test_delta_form_by_padded_order(monkeypatch, n, form):
    """On TPU the padded order alone picks the delta kernel's form (the
    row form up to its VMEM cap); off TPU the reference runs.  The forced
    kernel path takes the same form and equals the reference bitwise at
    the cap and just above it."""
    assert ops.delta_form(n) == "reference"
    assert ops.delta_order(n) == n
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.delta_form(n) == form
    assert ops.delta_order(n) == (n if form == "reference"
                                  else mosaic.padded_order(n))
    monkeypatch.undo()
    if n in (ROW_FORM_MAX_N, ROW_FORM_MAX_N + 1):
        rng = np.random.default_rng(n)
        C, M, ps, pairs = _padded_rows(rng, n, (n,), 2, 3)
        got = ops.qap_delta(C[0], M[0], ps, pairs, force_pallas=True,
                            interpret=True)
        want = ref.qap_delta_ref(C[0], M[0], ps, pairs)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_ops_objective_leading_batch_dispatch():
    """ops.qap_objective accepts (..., P, N) leading batch dims: the CPU
    path is bitwise-equal per permutation to qap.objective, and the
    forced-Pallas interpret path matches numerically."""
    rng = np.random.default_rng(6)
    n, batch, p_cnt = 27, 3, 4
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(0), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)

    got = ops.qap_objective(C, M, perms)
    assert got.shape == (batch, p_cnt)
    scalar = np.stack([[float(qap.objective(C, M, perms[i, j]))
                        for j in range(p_cnt)] for i in range(batch)])
    np.testing.assert_array_equal(np.asarray(got), scalar.astype(np.float32))

    # 4-D leading shape flattens to the same values
    got4 = ops.qap_objective(C, M, perms.reshape(3, 1, p_cnt, n))
    np.testing.assert_array_equal(np.asarray(got4).reshape(batch, p_cnt),
                                  np.asarray(got))

    # forced Pallas (interpret) leading-batch path agrees with the ref
    gotp = ops.qap_objective(C, M, perms, force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(gotp), np.asarray(got), rtol=1e-5)


def test_ops_objective_under_vmap_matches_flat_dispatch():
    """The wide-generation usage pattern: ops.qap_objective traced per
    island under an outer vmap (the eval="island" golden path and the
    batched solvers' instance axis) must equal the explicit leading-batch
    dispatch bitwise on the CPU path."""
    rng = np.random.default_rng(7)
    n, batch, p_cnt = 32, 4, 6
    C, M = _instance(rng, n, np.float32)
    perms = qap.random_permutations(jax.random.PRNGKey(1), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    per_island = jax.jit(jax.vmap(lambda p: ops.qap_objective(C, M, p)))
    flat = jax.jit(lambda: ops.qap_objective(C, M, perms))
    assert np.asarray(per_island(perms)).tobytes() == \
        np.asarray(flat()).tobytes()


# ------------------------------------------------------------ sparse kernels
def _sparse_instance(rng, n, density=0.25):
    C, M = _instance(rng, n, np.float32)
    C = jnp.asarray(np.where(rng.random((n, n)) < density,
                             np.asarray(C), 0.0).astype(np.float32))
    return sparse.from_dense(np.asarray(C)), C, M


@pytest.mark.parametrize("n", [16, 27, 45, 128])
@pytest.mark.parametrize("batch,p_cnt", [(1, 4), (3, 5)])
def test_objective_sparse_kernel_matches_ref(n, batch, p_cnt):
    """Interpret-mode gather kernel vs the jnp sparse ref (which is itself
    bitwise-equal to the dense ref on these integer instances)."""
    rng = np.random.default_rng(n + batch)
    S, C, M = _sparse_instance(rng, n)
    perms = qap.random_permutations(jax.random.PRNGKey(n), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    got = qap_objective_sparse_pallas_batch(S, M, perms, interpret=True)
    want = ref.qap_objective_sparse_ref(S, M, perms)
    assert got.shape == (batch, p_cnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(ref.qap_objective_ref(C, M,
                                                                   perms)))


@pytest.mark.parametrize("n", [16, 45, 128])
@pytest.mark.parametrize("batch,k", [(1, 8), (4, 12)])
def test_delta_sparse_kernel_matches_ref(n, batch, k):
    rng = np.random.default_rng(n + batch + k)
    S, C, M = _sparse_instance(rng, n)
    ps, pairs = _batched_candidates(rng, n, batch, k)
    got = qap_delta_sparse_pallas_batch(S, M, ps, pairs, interpret=True)
    want = ref.qap_delta_sparse_ref(S, M, ps, pairs)
    assert got.shape == (batch, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(ref.qap_delta_ref(C, M, ps,
                                                               pairs)))


def test_sparse_kernel_batch_instance_matrices():
    """S/M may carry the leading instance axis (the batched solvers'
    case) for both sparse kernels."""
    rng = np.random.default_rng(8)
    n, b0, p_cnt, rpt, k = 27, 3, 4, 2, 6
    per = [_sparse_instance(rng, n) for _ in range(b0)]
    S = sparse.from_dense(np.stack([np.asarray(c) for _, c, _ in per]))
    Ms = jnp.stack([m for _, _, m in per])
    perms = qap.random_permutations(jax.random.PRNGKey(3), b0 * p_cnt,
                                    n).reshape(b0, p_cnt, n)
    got = qap_objective_sparse_pallas_batch(S, Ms, perms, interpret=True)
    want = jnp.stack([ref.qap_objective_sparse_ref(
        jax.tree_util.tree_map(lambda x: x[b], S), Ms[b], perms[b])
        for b in range(b0)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    ps, pairs = _batched_candidates(rng, n, b0 * rpt, k)
    gotd = qap_delta_sparse_pallas_batch(S, Ms, ps, pairs, interpret=True)
    wantd = jnp.concatenate([
        ref.qap_delta_sparse_ref(
            jax.tree_util.tree_map(lambda x: x[r], S), Ms[r],
            ps[r * rpt:(r + 1) * rpt], pairs[r * rpt:(r + 1) * rpt])
        for r in range(b0)])
    np.testing.assert_allclose(np.asarray(gotd), np.asarray(wantd),
                               rtol=1e-4, atol=1e-3)


def test_ops_sparse_dispatch_forced_pallas():
    """The public sparse dispatches: CPU path bitwise-equal to the ref,
    forced-Pallas interpret path allclose, under-vmap fold included."""
    rng = np.random.default_rng(9)
    n, batch, p_cnt, k = 27, 3, 4, 8
    S, C, M = _sparse_instance(rng, n)
    perms = qap.random_permutations(jax.random.PRNGKey(5), batch * p_cnt,
                                    n).reshape(batch, p_cnt, n)
    got = ops.qap_objective_sparse(S, M, perms)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.qap_objective_sparse_ref(S, M,
                                                                 perms)))
    gotp = ops.qap_objective_sparse(S, M, perms, force_pallas=True,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(gotp), np.asarray(got), rtol=1e-5)

    ps, pairs = _batched_candidates(rng, n, batch, k)
    gotd = ops.qap_delta_sparse(S, M, ps, pairs)
    np.testing.assert_array_equal(
        np.asarray(gotd), np.asarray(ref.qap_delta_sparse_ref(S, M, ps,
                                                              pairs)))
    gotdp = ops.qap_delta_sparse(S, M, ps, pairs, force_pallas=True,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(gotdp), np.asarray(gotd),
                               rtol=1e-4, atol=1e-3)

    # vmapped dispatch folds into the leading batch (same values)
    vm = jax.vmap(lambda p: ops.qap_objective_sparse(S, M, p,
                                                     force_pallas=True,
                                                     interpret=True))
    np.testing.assert_allclose(np.asarray(vm(perms)), np.asarray(got),
                               rtol=1e-5)


# -------------------------------------------------- no pallas under vmap
def _count_pallas_calls(jaxpr):
    """Count pallas_call eqns in a jaxpr, descending into sub-jaxprs."""
    cnt = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            cnt += 1
        for v in eqn.params.values():
            leaves = jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr"))
            for leaf in leaves:
                if hasattr(leaf, "eqns"):
                    cnt += _count_pallas_calls(leaf)
                elif hasattr(leaf, "jaxpr"):
                    cnt += _count_pallas_calls(leaf.jaxpr)
    return cnt


def test_no_pallas_call_under_vmap_on_tpu_paths(monkeypatch):
    """Regression: on the TPU dispatch path no pallas_call may ever be
    batched by vmap.  jax's generic pallas batching rule silently falls
    back to a *sequential per-element loop* when a scalar-prefetch
    operand is batched (the delta kernel's case), so the dispatch layer
    (``ops``) must fold every vmap axis — chains, solvers, islands, and
    the batched solvers' instance axis — into the kernels' leading batch
    instead.  Trace-level check over the three batch solvers (and the
    batched polish): the pallas batching rule must never fire while
    pallas_calls are present in the trace.
    """
    from dataclasses import replace
    from jax._src.interpreters import batching
    from jax._src.pallas.pallas_call import pallas_call_p
    from repro.core import annealing, composite, genetic, mapping
    import repro.kernels.ops as kops
    from _fixtures import SA_SMALL, GA_SMALL, PCA_SMALL

    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    hits = []
    orig = batching.fancy_primitive_batchers[pallas_call_p]

    def spy(*args, **kwargs):
        hits.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setitem(batching.fancy_primitive_batchers, pallas_call_p,
                        spy)

    # jit trace caches are keyed on signatures only — a cached CPU-path
    # jaxpr from another test would bypass the patched _on_tpu (and the
    # TPU-path jaxprs traced here must not leak to later tests either).
    jax.clear_caches()
    try:
        # num_processes=3 keeps every signature unique to this test.
        B, n, procs = 2, 8, 3
        Cs = jnp.ones((B, n, n), jnp.float32)
        Ms = jnp.ones((B, n, n), jnp.float32)
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
        nvs = jnp.full((B,), n, jnp.int32)
        sa = replace(SA_SMALL, solvers=3)
        pca = replace(PCA_SMALL, ga=replace(GA_SMALL, tournament=3))
        pca_fused = replace(
            pca, sa=replace(pca.sa, loop="fused"),
            ga=replace(pca.ga, eval="fused"))
        Ss = sparse.from_dense(np.asarray(Cs))
        # The fused steps do not compile for TPU: there they must refuse
        # loudly rather than trace (or silently fall back).
        fused = {
            "psa_fused": lambda: annealing.run_psa_batch(
                Cs, Ms, keys, replace(sa, loop="fused"), procs,
                n_valid=nvs),
            "pga_fused": lambda: genetic.run_pga_batch(
                Cs, Ms, keys, replace(GA_SMALL, eval="fused"), procs,
                n_valid=nvs),
            "pca_fused": lambda: composite.run_pca_batch(
                Cs, Ms, keys, pca_fused, procs, n_valid=nvs),
        }
        for name, fn in fused.items():
            with pytest.raises(NotImplementedError, match="2D gather"):
                jax.make_jaxpr(fn)()
        solvers = {
            "psa": lambda: annealing.run_psa_batch(Cs, Ms, keys, sa, procs,
                                                   n_valid=nvs),
            "psa_sparse": lambda: annealing.run_psa_batch(
                Ss, Ms, keys, replace(sa, flows="sparse"), procs,
                n_valid=nvs),
            "pga": lambda: genetic.run_pga_batch(Cs, Ms, keys, GA_SMALL,
                                                 procs, n_valid=nvs),
            "pca": lambda: composite.run_pca_batch(Cs, Ms, keys, pca, procs,
                                                   n_valid=nvs),
            "polish": lambda: mapping.polish_batch(
                Cs, Ms,
                jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n)),
                keys, 3, nvs),
        }
        for name, fn in solvers.items():
            hits.clear()
            jaxpr = jax.make_jaxpr(fn)()
            assert _count_pallas_calls(jaxpr.jaxpr) > 0, \
                f"{name}: TPU path traced no pallas_call — check dead dispatch"
            assert not hits, \
                f"{name}: pallas_call was batched by vmap ({len(hits)} times)"

        # Positive control: vmapping a raw kernel must hit the batching
        # rule, otherwise this test could pass while asserting nothing.
        hits.clear()
        C1 = jnp.ones((n, n), jnp.float32)
        p = jnp.arange(n, dtype=jnp.int32)
        pairs = jnp.zeros((4, 2), jnp.int32)
        jax.make_jaxpr(jax.vmap(
            lambda pp: qap_delta_pallas(C1, C1, pp, pairs)))(jnp.stack([p, p]))
        assert hits, "spy failed to observe the pallas batching rule"
    finally:
        jax.clear_caches()   # drop the TPU-path traces (never executable here)


# ---------------------------------------------------------------- selective scan
from repro.kernels.selective_scan import selective_scan_pallas


@pytest.mark.parametrize("shape", [(1, 128, 512, 4), (2, 256, 512, 16),
                                   (2, 128, 1024, 16)])
def test_selective_scan_kernel_matches_ref(shape):
    bsz, s, d, n = shape
    rng = np.random.default_rng(sum(shape))
    u = jnp.asarray(rng.standard_normal((bsz, s, d)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (bsz, s, d)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.1, 1.0, (d, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32)
    got = selective_scan_pallas(u, dt, a, b, c, interpret=True)
    want = ref.selective_scan_ref(u, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_kernel_dtypes(dtype):
    bsz, s, d, n = 1, 128, 512, 8
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((bsz, s, d)), jnp.float32).astype(dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (bsz, s, d)), jnp.float32).astype(dtype)
    a = jnp.asarray(-rng.uniform(0.1, 1.0, (d, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32).astype(dtype)
    c = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32).astype(dtype)
    got = selective_scan_pallas(u, dt, a, b, c, interpret=True)
    want = ref.selective_scan_ref(u, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_selective_scan_matches_model_path():
    """Kernel semantics == the model's chunked XLA scan (ssm._scan_chunked)."""
    from repro.models import ssm
    bsz, s, d, n = 2, 256, 512, 8
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((bsz, s, d)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (bsz, s, d)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.1, 1.0, (d, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, s, n)), jnp.float32)
    a_bar = jnp.exp(dt[..., None] * a[None, None])
    bx = (dt * u)[..., None] * b[:, :, None, :]
    y_model, _ = ssm._scan_chunked(a_bar, bx,
                                   jnp.zeros((bsz, d, n), jnp.float32), c)
    y_kernel = selective_scan_pallas(u, dt, a, b, c, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=2e-4, atol=2e-4)
