"""The multilevel path's shapes and odd orders: every order coarsens to
``coarse_n``, the device programs are fixed by the order (a second solve
of an order traces and compiles nothing), and at power-of-two orders the
pinned ELL width and the jitted polish leave the result bitwise equal to
the path that took the width from the data (docs/DESIGN.md §10)."""
import contextlib
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


@pytest.mark.parametrize("n", [64, 128])
def test_power_of_two_orders_match_the_data_width_path(n):
    C, M = job(n, 11 + n)
    assert sparse.ell_width(C, n) > sparse.max_degree(C)   # really padded
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
        assert sparse.max_degree(C) <= sparse.ell_width(C, 2048)
        widths.add(sparse.ell_width(C, 2048))
    assert widths == {384}
    assert sparse.ell_width(np.ones((40, 40)), 64) == 64    # capped
    skewed = np.zeros((300, 300), np.float32)   # one row above its class
    skewed[0, 1:201] = 1.0
    assert sparse.ell_width(skewed, 512) == 256
    assert sparse.ell_width(np.zeros((5, 5)), 8) == 8


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
