"""The multilevel path's shapes and odd orders: every order coarsens to
``coarse_n``, the device programs are fixed by the order (a second solve
of an order traces and compiles nothing), and at power-of-two orders the
pinned ELL width and the jitted polish leave the result bitwise equal to
the path that took the width from the data.  The host steps run on the
flows' nonzeros; the dense host steps they replaced are kept here as the
reference they are held to, bit for bit (docs/DESIGN.md §10)."""
import contextlib
import math
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import annealing, mapping, multilevel, sparse

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import reference  # noqa: E402
import traffic  # noqa: E402

CFG = multilevel.MultilevelConfig(
    coarse_n=16,
    coarse_sa=annealing.SAConfig(max_neighbors=4, iters_per_exchange=2,
                                 num_exchanges=2, solvers=2),
    refine_sa=annealing.SAConfig(max_neighbors=4, iters_per_exchange=2,
                                 num_exchanges=2, solvers=2, flows="sparse"),
    final_polish_rounds=8)

GRID = reference.grid_distances([8, 8, 8])


def job(n: int, seed: int):
    """The benchmark's flows for an order-n job, on n random nodes of an
    8x8x8 mesh."""
    nodes = np.sort(np.random.default_rng(seed).choice(512, n, replace=False))
    return (traffic.ring_background_flows(n, seed),
            GRID[np.ix_(nodes, nodes)])


def f64(C, M, p):
    return float((C.astype(np.float64)
                  * M.astype(np.float64)[np.ix_(p, p)]).sum())


def data_width_path(C, M, key, cfg):
    """The pipeline as it was when each level's ELL width came from its
    densest row and the polish was traced on every call (even orders)."""
    stack, Cl, Ml = [], C, M
    while Cl.shape[0] > cfg.coarse_n:
        fp = multilevel.heavy_edge_matching(Cl)
        sp = multilevel.closest_pair_matching(Ml)
        stack.append((Cl, Ml, fp, sp))
        Cl, Ml = multilevel.coarsen(Cl, Ml, fp, sp)
    p, _, _ = annealing.run_psa(jnp.asarray(Cl), jnp.asarray(Ml),
                                jax.random.fold_in(key, 0), cfg.coarse_sa,
                                cfg.num_processes)
    p = np.asarray(p)
    for li, (Cl, Ml, fp, sp) in enumerate(reversed(stack)):
        p = multilevel.prolong_perm(p, fp, sp)
        p, _, _ = annealing.run_psa(
            sparse.from_dense(Cl), jnp.asarray(Ml), jax.random.fold_in(
                key, 1 + li), cfg.refine_sa, cfg.num_processes,
            init_perm=jnp.asarray(p, jnp.int32))
        p = np.asarray(p)
    p, _ = mapping.polish(
        sparse.from_dense(C), jnp.asarray(M), jnp.asarray(p, jnp.int32),
        jax.random.fold_in(key, 7), rounds=cfg.final_polish_rounds)
    p = np.asarray(p)
    return p, f64(C, M, p)


# ---- the dense host steps: every pass over the (n, n) flow matrix, as
# the pipeline ran them before it carried the flows' nonzeros.

def dense_heavy_edge_matching(C):
    n = C.shape[0]
    W = C.astype(np.float64)
    W = W + W.T
    np.fill_diagonal(W, 0.0)
    matched = np.zeros(n, dtype=bool)
    pairs = []
    for v in np.argsort(-W.sum(axis=1), kind="stable"):
        if matched[v]:
            continue
        w = np.where(matched, -1.0, W[v])
        w[v] = -1.0
        u = int(np.argmax(w))
        if w[u] <= 0.0:
            continue
        matched[v] = matched[u] = True
        pairs.append((int(v), u))
    left = np.where(~matched)[0]
    pairs.extend((int(left[i]), int(left[i + 1]))
                 for i in range(0, len(left), 2))
    return np.asarray(pairs, dtype=np.int64)


def dense_match_level(C, M):
    n = C.shape[0]
    if n % 2 == 0:
        return (dense_heavy_edge_matching(C),
                multilevel.closest_pair_matching(M), None)
    W = C.astype(np.float64)
    u = int(np.argmin(W.sum(axis=0) + W.sum(axis=1)))
    w = int(np.argmax(M.astype(np.float64).sum(axis=1)))
    procs = np.delete(np.arange(n), u)
    nodes = np.delete(np.arange(n), w)
    return (procs[dense_heavy_edge_matching(C[np.ix_(procs, procs)])],
            nodes[multilevel.closest_pair_matching(M[np.ix_(nodes, nodes)])],
            (u, w))


def dense_coarsen(C, M, flow_pairs, sys_pairs):
    n, nc = C.shape[0], flow_pairs.shape[0]
    cid = np.full(n, -1, dtype=np.int64)
    cid[flow_pairs[:, 0]] = np.arange(nc)
    cid[flow_pairs[:, 1]] = np.arange(nc)
    ii, jj = np.nonzero(C)
    kept = (cid[ii] >= 0) & (cid[jj] >= 0)
    ii, jj = ii[kept], jj[kept]
    Cc = np.zeros((nc, nc), dtype=np.float64)
    np.add.at(Cc, (cid[ii], cid[jj]), C[ii, jj].astype(np.float64))
    np.fill_diagonal(Cc, 0.0)
    return Cc.astype(np.float32), multilevel.contract_distances(M, sys_pairs)


def dense_ell_width(C, order):
    n, nnz = C.shape[0], int(np.count_nonzero(C))
    if nnz == 0:
        return min(order, 128)
    density_class = 2.0 ** math.ceil(math.log2(nnz / (n * n)))
    width = min(order, -(-math.ceil(1.5 * density_class * order) // 128)
                * 128)
    d = sparse.max_degree(C)
    return min(order, 1 << (d - 1).bit_length()) if d > width else width


def pad(A, order):
    return np.pad(A, ((0, order - A.shape[0]),) * 2)


def pad_perm(p, order):
    return np.concatenate([p, np.arange(len(p), order)]).astype(np.int32)


def dense_host_path(C, M, key, cfg):
    """``solve_multilevel`` with the dense host steps: the same device
    programs, keys and padding; (perm, F, coarse F, levels, identity F)."""
    stack, Cl, Ml = [], C, M
    while Cl.shape[0] > cfg.coarse_n and len(stack) < cfg.max_levels:
        fp, sp, pinned = dense_match_level(Cl, Ml)
        stack.append((Cl, Ml, fp, sp, pinned))
        Cl, Ml = dense_coarsen(Cl, Ml, fp, sp)

    def ell(A):
        order = multilevel.level_order(A.shape[0])
        return sparse.from_dense(pad(A, order), dense_ell_width(A, order))

    flows = [ell(C)] + [ell(s[0]) for s in stack[1:]]
    nc = Cl.shape[0]
    order = multilevel.level_order(nc)
    out = annealing.run_psa(
        jnp.asarray(pad(Cl, order)), jnp.asarray(pad(Ml, order)),
        jax.random.fold_in(key, 0), cfg.coarse_sa, cfg.num_processes,
        n_valid=None if order == nc else np.int32(nc),
        counts=annealing.resolved_loop(cfg.coarse_sa, order) == "event")
    p = np.asarray(out[0])[:nc]
    coarse_f = f64(Cl, Ml, p)
    levels = []
    for li, ((Cl, Ml, fp, sp, pinned), Cs) in enumerate(
            zip(reversed(stack), reversed(flows))):
        nl, order = Cl.shape[0], multilevel.level_order(Cl.shape[0])
        p = multilevel.prolong_perm(p, fp, sp, pinned)
        f_pro = f64(Cl, Ml, p)
        out = annealing.run_psa(
            Cs, jnp.asarray(pad(Ml, order)), jax.random.fold_in(key, 1 + li),
            cfg.refine_sa, cfg.num_processes,
            n_valid=None if order == nl else np.int32(nl),
            init_perm=jnp.asarray(pad_perm(p, order)),
            counts=annealing.resolved_loop(cfg.refine_sa) == "event")
        p = np.asarray(out[0])[:nl]
        levels.append(multilevel.LevelInfo(
            n=nl, nnz=int(np.count_nonzero(Cl)), f_prolonged=f_pro,
            f_refined=f64(Cl, Ml, p)))
    n = C.shape[0]
    order = multilevel.level_order(n)
    out = mapping.polish_jit(
        flows[0], jnp.asarray(pad(M, order)), jnp.asarray(pad_perm(p, order)),
        jax.random.fold_in(key, 7), rounds=cfg.final_polish_rounds,
        n_valid=None if order == n else np.int32(n))
    p = np.asarray(out[0])[:n]
    f, f_identity = f64(C, M, p), f64(C, M, np.arange(n))
    if f > f_identity:
        p, f = np.arange(n), f_identity
    return p, f, coarse_f, tuple(levels), f_identity


@pytest.mark.parametrize("n", [64, 97, 128, 305])
def test_solve_matches_the_dense_host_steps(n):
    """Even, odd and non-power-of-two orders of the benchmark's flows:
    perm, F, coarse F, every level and the identity's F bit for bit."""
    C, M = job(n, 3 * n)
    key = jax.random.PRNGKey(n + 1)
    spans = Spans()
    res = multilevel.solve_multilevel(C, M, key, CFG, span=spans)
    p, f, coarse_f, levels, f_identity = dense_host_path(C, M, key, CFG)
    np.testing.assert_array_equal(res.perm, p)
    assert res.objective == f and res.coarse_objective == coarse_f
    assert res.levels == levels and len(levels) >= 2
    assert res.identity_objective == f_identity
    (coarsening,) = spans.attrs("ml.coarsen")
    assert coarsening["nnz"] == np.count_nonzero(C)


def integer_flows(kind, n, seed):
    """Integer-valued flows: the benchmark's, asymmetric with many tied
    weights, with isolated processes (zero rows and columns) and a
    nonzero diagonal, or none at all."""
    rng = np.random.default_rng(seed)
    if kind == "bench":
        return traffic.ring_background_flows(n, seed)
    if kind == "zero":
        return np.zeros((n, n), np.float32)
    C = (rng.random((n, n)) < 0.3) * rng.integers(1, 4, (n, n))
    if kind == "isolated":
        lone = rng.choice(n, n // 4, replace=False)
        C[lone, :] = 0
        C[:, lone] = 0
        np.fill_diagonal(C, rng.integers(0, 3, n))
    return C.astype(np.float32)


FLOW_CASES = [("bench", 64), ("bench", 130), ("ties", 40), ("ties", 41),
              ("isolated", 48), ("isolated", 33), ("zero", 10)]


@pytest.mark.parametrize("kind,n", FLOW_CASES)
def test_objective_over_nonzeros_equals_dense(kind, n):
    C = integer_flows(kind, n, n)
    M = GRID[np.ix_(np.arange(n), np.arange(n))]
    flows = sparse.nonzeros(C)
    assert flows.nnz == np.count_nonzero(C)
    rng = np.random.default_rng(n)
    for p in [np.arange(n)] + [rng.permutation(n) for _ in range(4)]:
        assert multilevel.objective(flows, M, p) == f64(C, M, p)


@pytest.mark.parametrize("n", [40, 97])
def test_non_integer_flows_keep_f_close_and_the_perm_valid(n):
    """Exactness needs integer flows; on others F over the nonzeros is
    summed in another order than the dense sum, so it stays within a few
    ulps of it, and the solve still returns a permutation no worse than
    the identity with F that matches it."""
    rng = np.random.default_rng(n)
    C = ((rng.random((n, n)) < 0.2)
         * rng.random((n, n)) * 7.3).astype(np.float32)
    M = GRID[np.ix_(np.arange(n), np.arange(n))]
    flows = sparse.nonzeros(C)
    for p in [np.arange(n)] + [rng.permutation(n) for _ in range(4)]:
        assert multilevel.objective(flows, M, p) == pytest.approx(
            f64(C, M, p), rel=1e-13)
    res = multilevel.solve_multilevel(C, M, jax.random.PRNGKey(n), CFG)
    np.testing.assert_array_equal(np.sort(res.perm), np.arange(n))
    assert res.objective == pytest.approx(f64(C, M, res.perm), rel=1e-13)
    assert res.identity_objective == pytest.approx(
        f64(C, M, np.arange(n)), rel=1e-13)
    assert res.objective <= res.identity_objective


@pytest.mark.parametrize("kind,n", [c for c in FLOW_CASES if c[1] % 2 == 0])
def test_heavy_edge_pairs_equal_the_dense_matching(kind, n):
    C = integer_flows(kind, n, n + 1)
    want = dense_heavy_edge_matching(C)
    np.testing.assert_array_equal(multilevel.heavy_edge_matching(C), want)
    np.testing.assert_array_equal(
        multilevel.heavy_edge_pairs(sparse.nonzeros(C)), want)


@pytest.mark.parametrize("kind,n", FLOW_CASES)
def test_match_and_contract_equal_the_dense_steps(kind, n):
    """A level's matchings (odd orders pin a pair) and its contraction,
    three levels down."""
    C = integer_flows(kind, n, n + 2)
    M = GRID[np.ix_(np.arange(n), np.arange(n))]
    flows = sparse.nonzeros(C)
    while C.shape[0] > 4:
        fp, sp, pinned = multilevel.match_flows(flows, M)
        fp_d, sp_d, pinned_d = dense_match_level(C, M)
        np.testing.assert_array_equal(fp, fp_d)
        np.testing.assert_array_equal(sp, sp_d)
        assert pinned == pinned_d
        Cc, Mc = dense_coarsen(C, M, fp, sp)
        np.testing.assert_array_equal(multilevel.coarsen(C, M, fp, sp)[0], Cc)
        flows = multilevel.contract(flows, fp)
        np.testing.assert_array_equal(flows.dense(), Cc)
        assert flows.nnz == np.count_nonzero(Cc)
        C, M = Cc, Mc


@pytest.mark.parametrize("n", [64, 128])
def test_power_of_two_orders_match_the_data_width_path(n):
    C, M = job(n, 11 + n)
    assert sparse.ell_width(sparse.nonzeros(C), n) > sparse.max_degree(C)
    key = jax.random.PRNGKey(n)
    res = multilevel.solve_multilevel(C, M, key, CFG)
    p, f = data_width_path(C, M, key, CFG)
    np.testing.assert_array_equal(res.perm, p)
    assert res.objective == f


class Spans:
    """A span hook that keeps each span's name and attributes."""

    def __init__(self):
        self.got = []

    @contextlib.contextmanager
    def __call__(self, name, **attrs):
        rec = type("Rec", (), {"set": lambda s, **a: attrs.update(a)})()
        yield rec
        self.got.append((name, attrs))

    def attrs(self, name):
        return [a for got, a in self.got if got == name]


@pytest.mark.parametrize("n", [33, 67, 130])
def test_odd_orders_coarsen_to_coarse_n(n):
    C, M = job(n, n)
    spans = Spans()
    res = multilevel.solve_multilevel(C, M, jax.random.PRNGKey(3), CFG,
                                      span=spans)
    (coarsening,) = spans.attrs("ml.coarsen")
    assert coarsening["n"] == n and coarsening["coarse_n"] <= CFG.coarse_n
    assert coarsening["levels"] == len(res.levels) >= 1
    assert res.levels[-1].n == n and res.levels[0].n // 2 <= CFG.coarse_n
    assert [a["n"] for a in spans.attrs("ml.level")] == [
        lv.n for lv in res.levels]
    assert sorted(res.perm.tolist()) == list(range(n))
    assert res.objective == f64(C, M, res.perm)
    assert res.objective <= f64(C, M, np.arange(n))
    for lv in res.levels:
        assert lv.f_refined <= lv.f_prolonged


def test_an_odd_level_pins_one_process_on_one_node():
    C, M = job(9, 2)
    fp, sp, pinned = multilevel.match_level(C, M)
    assert fp.shape == sp.shape == (4, 2)
    u, w = pinned
    assert sorted(fp.ravel().tolist() + [u]) == list(range(9))
    assert sorted(sp.ravel().tolist() + [w]) == list(range(9))
    p = multilevel.prolong_perm(np.random.default_rng(0).permutation(4),
                                fp, sp, pinned)
    assert p[u] == w and sorted(p.tolist()) == list(range(9))
    Cc, Mc = multilevel.coarsen(C, M, fp, sp)
    assert Cc.shape == Mc.shape == (4, 4)


_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_events = []


def _on_event(event, duration, **kw):
    if event in (_COMPILE, _TRACE):
        _events.append((threading.get_ident(), event))


jax.monitoring.register_event_duration_secs_listener(_on_event)


@pytest.mark.parametrize("n,other", [(100, 77), (128, 128)])
def test_a_second_solve_of_an_order_compiles_nothing(n, other):
    """Orders that share a power of two share every program: after one
    solve, another order-``other`` job with other flows and other nodes
    traces and compiles nothing."""
    C, M = job(n, 5)
    spans = Spans()
    multilevel.solve_multilevel(C, M, jax.random.PRNGKey(0), CFG, span=spans)
    C2, M2 = job(other, 6)
    del _events[:]
    multilevel.solve_multilevel(C2, M2, jax.random.PRNGKey(9), CFG,
                                span=spans)
    mine = [e for t, e in _events if t == threading.get_ident()]
    assert mine == []
    widths = {a["ell_width"] for a in spans.attrs("ml.coarsen")}
    assert len(widths) == 1


def test_ell_width_is_fixed_by_order_and_density_class():
    rng = np.random.default_rng(0)
    widths = set()
    for n in (1137, 1449, 2048):            # one power of two, one class
        C = traffic.ring_background_flows(n, int(rng.integers(1 << 30)))
        width = sparse.ell_width(sparse.nonzeros(C), 2048)
        assert sparse.max_degree(C) <= width
        widths.add(width)
    assert widths == {384}
    assert sparse.ell_width(sparse.nonzeros(np.ones((40, 40))), 64) == 64
    skewed = np.zeros((300, 300), np.float32)   # one row above its class
    skewed[0, 1:201] = 1.0
    assert sparse.ell_width(sparse.nonzeros(skewed), 512) == 256
    assert sparse.ell_width(sparse.nonzeros(np.zeros((5, 5))), 8) == 8


def test_bucket_waves_are_unchanged():
    """The dense bucket path shares nothing that changed: a wave of
    orders 5 to 64 gives the perms and F whose digest was recorded before
    the multilevel shapes were fixed by the order."""
    import hashlib
    from repro.serve import MappingEngine, MapRequest
    eng = MappingEngine(buckets=(32, 64), polish_rounds=16,
                        sa_cfg=annealing.SAConfig(
                            max_neighbors=6, iters_per_exchange=3,
                            num_exchanges=3, solvers=2))
    rng = np.random.default_rng(7)
    futures = []
    for i, n in enumerate([5, 17, 31, 32, 40, 64, 64]):
        nodes = np.sort(rng.choice(512, n, replace=False))
        futures.append(eng.submit(MapRequest(
            job_id=f"j{i}", C=traffic.ring_background_flows(n, i),
            M=GRID[np.ix_(nodes, nodes)], seed=i)))
    eng.flush()
    h = hashlib.sha256()
    for f in futures:
        r = f.result()
        h.update(np.asarray(r.perm, np.int32).tobytes())
        h.update(np.float64(r.objective).tobytes())
    assert h.hexdigest() == (
        "cbe5b91302498ac67172720b0d24d04ac505fb7b9dc24830b88c1a193c2c4e6b")
