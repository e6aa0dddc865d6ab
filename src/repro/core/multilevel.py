"""Multilevel coarsen → map → refine pipeline for large mapping instances.

The paper's experiments top out at order 729 because every solver pass
works on the full dense instance.  Glantz–Meyerhenke–Noe (arXiv:1411.0921)
show the standard way to scale process mapping: contract the program
graph level by level, solve the small coarse instance well, then prolong
the solution back up and *refine* it at every level.  This module is that
pipeline over the repo's existing machinery (docs/DESIGN.md §10):

* **Coarsening** (host-side numpy, like instance generation, on the flows'
  nonzeros: ``sparse.Nonzeros``, built from the dense C once, so every
  host step is O(nnz) and the dense-signature functions are wrappers):
  heavy-edge matching on the flow graph — repeatedly pair each vertex
  with its heaviest unmatched neighbour, so the strongest flows disappear
  *inside* clusters and the coarse objective tracks the fine one — and a
  matching closest-pair contraction of the system graph, with the coarse
  distance between clusters the minimum member distance.  Matchings are
  perfect (every cluster has exactly 2 members; levels halve), so
  prolongation is a permutation by construction.  An odd order first pins
  its lightest process on its most remote node (``match_flows``): the
  pair stays out of the coarser levels and is placed together on
  prolongation, and the rest halves.  Every order coarsens down to
  ``coarse_n``.
* **Coarse solve**: the existing batched solvers (``run_psa``/``run_pga``)
  on the dense coarse instance — at ``coarse_n`` the dense path is the
  fast one.
* **Shapes**: every device program takes its shapes from the order
  alone.  A level of order n is solved at the next power of two
  (``level_order``), padded with zero flows and masked by ``n_valid``;
  its ELL width comes from that order and the flows' density class
  (``sparse.ell_width``), never from the densest row.  So one deployment
  meets a short, fixed list of programs (docs/DESIGN.md §10).
* **Refinement**: prolong one level and warm-start SA via the solvers'
  ``init_perm`` argument.  Chain 0 of every process starts from the
  prolonged permutation, so the refined objective can never end above it
  (the never-worse-than-seed guarantee PR 2 established, now load-bearing:
  each level provably improves on its coarse seed, tested on the
  known-optimum ``exact.make_torus`` instances).  Refinement runs on the
  **sparse** representation (``SAConfig.flows="sparse"``) — O(nnz) per
  candidate — which is what keeps n=4096 interactive.
* **Final polish**: the finest level ends with the batched 2-swap descent
  (``mapping.polish_jit``), also through the sparse dispatches.

``span`` (``solve_multilevel``'s hook, ``serve.telemetry.span`` in the
engine) times the host coarsening (``ml.coarsen``), each refinement level
(``ml.level``) and every wait for the device's results (``engine.fetch``,
with the event loop's counts).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import annealing, genetic, mapping, sparse

Array = jax.Array


@dataclass(frozen=True)
class MultilevelConfig:
    coarse_n: int = 64            # stop coarsening at or below this order
    max_levels: int = 12          # safety bound on the level stack
    algorithm: str = "psa"        # coarse solver: "psa" | "pga"
    num_processes: int = 2
    coarse_sa: annealing.SAConfig = field(default=annealing.SAConfig(
        max_neighbors=30, iters_per_exchange=20, num_exchanges=10, solvers=8))
    coarse_ga: genetic.GAConfig = field(default=genetic.GAConfig(
        generations=60, pop_size=0))
    refine_sa: annealing.SAConfig = field(default=annealing.SAConfig(
        max_neighbors=16, iters_per_exchange=8, num_exchanges=4, solvers=2,
        flows="sparse"))
    final_polish_rounds: int = 64


class LevelInfo(NamedTuple):
    n: int                # order at this level
    nnz: int              # stored flow nonzeros at this level
    f_prolonged: float    # objective of the prolonged coarse solution
    f_refined: float      # objective after warm-started refinement
                          # (never above f_prolonged)


class MultilevelResult(NamedTuple):
    perm: np.ndarray          # finest-level permutation
    objective: float          # F(perm) on the input instance (exact, f64)
    coarse_objective: float   # objective of the coarsest-level solve
    levels: Tuple[LevelInfo, ...]   # coarsest-to-finest refinement trace
    seconds: float
    identity_objective: float  # F of the identity (exact, f64)


def objective(flows: sparse.Nonzeros, M: np.ndarray, p) -> float:
    """Float64 (host) objective over the flows' nonzeros, the reporting
    and guarantee yardstick: F = sum C[i, j] M[p[i], p[j]].  With
    integer flows and distances every partial sum is exact, so F equals
    the dense sum bit for bit; other values are summed in another order
    than the dense sum's and may differ from it in the last places."""
    p = np.asarray(p, np.int64)
    at = p.take(flows.rows) * M.shape[1] + p.take(flows.cols)
    return float(np.dot(flows.vals.astype(np.float64),
                        np.ravel(M).take(at).astype(np.float64)))


def _flow_graph(flows: sparse.Nonzeros):
    """W = C + C^T without its diagonal, in float64, row-major:
    (row starts (n + 1,), neighbours, weights, row totals).  C's entries
    and C^T's, two row-major runs, coalesced: an entry of both sums
    C[i, j] + C[j, i], in that order."""
    def off_diagonal(f):
        off = f.rows != f.cols
        return f.rows[off], f.cols[off], f.vals[off]

    W = sparse.coalesce(flows.n, *map(np.concatenate, zip(
        off_diagonal(flows), off_diagonal(flows.T))), dtype=np.float64)
    starts = np.zeros(flows.n + 1, np.int64)
    np.cumsum(W.deg, out=starts[1:])
    return starts, W.cols, W.vals, np.bincount(W.rows, weights=W.vals,
                                               minlength=flows.n)


def heavy_edge_pairs(flows: sparse.Nonzeros) -> np.ndarray:
    """Perfect heavy-edge matching of the flow graph: (n//2, 2) pairs.

    Vertices are visited by descending total flow in W = C + C^T
    (stable, so ties are deterministic); each picks its heaviest
    unmatched neighbour, the lowest index among equal weights.  Vertices
    left without a positive-weight partner are paired among themselves
    in index order — the matching is always perfect (``n`` must be
    even).  Each visit reads the vertex's neighbour list, not a dense
    row.
    """
    n = flows.n
    if n % 2 != 0:
        raise ValueError(f"heavy-edge matching needs an even order, got {n}")
    starts, nbrs, weights, total = _flow_graph(flows)
    starts = starts.tolist()
    matched = np.zeros(n, dtype=bool)
    pairs = []
    for v in np.argsort(-total, kind="stable").tolist():
        s, e = starts[v], starts[v + 1]
        if matched[v] or s == e:
            continue
        us = nbrs[s:e]
        w = np.where(matched[us], -1.0, weights[s:e])
        k = int(np.argmax(w))
        if w[k] <= 0.0:
            continue                      # no unmatched positive neighbour
        u = int(us[k])
        matched[v] = matched[u] = True
        pairs.append((v, u))
    left = np.flatnonzero(~matched)
    pairs.extend((int(left[i]), int(left[i + 1]))
                 for i in range(0, len(left), 2))
    return np.asarray(pairs, dtype=np.int64)


def heavy_edge_matching(C: np.ndarray) -> np.ndarray:
    """``heavy_edge_pairs`` of a dense flow matrix."""
    return heavy_edge_pairs(sparse.nonzeros(C))


def closest_pair_matching(M: np.ndarray) -> np.ndarray:
    """Perfect matching of system nodes by ascending distance: (n//2, 2).

    Greedy in index order: each unmatched node grabs its nearest unmatched
    peer, so cluster members are topologically close and the coarse
    distance (minimum member distance) stays faithful.
    """
    n = M.shape[0]
    if n % 2 != 0:
        raise ValueError(f"closest-pair matching needs an even order, got {n}")
    matched = np.zeros(n, dtype=bool)
    pairs = []
    for i in range(n):
        if matched[i]:
            continue
        d = np.where(matched, np.inf, M[i].astype(np.float64))
        d[i] = np.inf
        j = int(np.argmin(d))
        matched[i] = matched[j] = True
        pairs.append((i, j))
    return np.asarray(pairs, dtype=np.int64)


def match_flows(flows: sparse.Nonzeros, M: np.ndarray):
    """One level's matchings at any order: (flow_pairs, sys_pairs, pinned).

    An even order is matched whole and ``pinned`` is None.  An odd order
    first sets aside its lightest process (least total flow) and its most
    remote node (largest distance sum): ``pinned = (process, node)``, the
    process placed on the node when the level is prolonged, and both left
    out of the coarser levels; the rest is matched as an even order.
    """
    n = flows.n
    if n % 2 == 0:
        return heavy_edge_pairs(flows), closest_pair_matching(M), None
    v = flows.vals.astype(np.float64)
    u = int(np.argmin(np.bincount(flows.cols, weights=v, minlength=n)
                      + np.bincount(flows.rows, weights=v, minlength=n)))
    w = int(np.argmax(M.astype(np.float64).sum(axis=1)))
    keep = (flows.rows != u) & (flows.cols != u)
    rows, cols = flows.rows[keep], flows.cols[keep]
    rest = sparse.Nonzeros(n=n - 1, rows=rows - (rows > u),
                           cols=cols - (cols > u), vals=flows.vals[keep])
    procs = np.delete(np.arange(n), u)
    nodes = np.delete(np.arange(n), w)
    return (procs[heavy_edge_pairs(rest)],
            nodes[closest_pair_matching(M[np.ix_(nodes, nodes)])], (u, w))


def match_level(C: np.ndarray, M: np.ndarray):
    """``match_flows`` of a dense flow matrix."""
    return match_flows(sparse.nonzeros(C), M)


def contract(flows: sparse.Nonzeros,
             flow_pairs: np.ndarray) -> sparse.Nonzeros:
    """Contract one level's flows: they sum over cluster pairs, and
    intra-cluster flows vanish (they cost the same under every coarse
    assignment up to the member distance the refinement level
    re-exposes).  A process in no pair (an odd level's pinned one) drops
    out with its flows.  O(nnz): each entry is mapped to its clusters
    and the duplicates summed."""
    nc = flow_pairs.shape[0]
    cid = np.full(flows.n, -1, dtype=np.int32)
    cid[flow_pairs[:, 0]] = np.arange(nc)
    cid[flow_pairs[:, 1]] = np.arange(nc)
    rows, cols = cid.take(flows.rows), cid.take(flows.cols)
    kept = (rows >= 0) & (cols >= 0) & (rows != cols)
    return sparse.coalesce(nc, rows[kept], cols[kept], flows.vals[kept])


def contract_distances(M: np.ndarray, sys_pairs: np.ndarray) -> np.ndarray:
    """Contract one level's distances: the minimum member distance, an
    optimistic (admissible) coarse proxy, with a zero diagonal."""
    a0, a1 = sys_pairs[:, 0], sys_pairs[:, 1]
    Mc = np.minimum.reduce([M[np.ix_(a0, a0)], M[np.ix_(a0, a1)],
                            M[np.ix_(a1, a0)], M[np.ix_(a1, a1)]])
    Mc = Mc.astype(np.float64)
    np.fill_diagonal(Mc, 0.0)
    return Mc.astype(np.float32)


def coarsen(C: np.ndarray, M: np.ndarray, flow_pairs: np.ndarray,
            sys_pairs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``contract`` and ``contract_distances`` of a dense level: the
    coarse (C, M), dense."""
    return (contract(sparse.nonzeros(C), flow_pairs).dense(),
            contract_distances(M, sys_pairs))


def prolong_perm(pc: np.ndarray, flow_pairs: np.ndarray,
                 sys_pairs: np.ndarray,
                 pinned: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """Lift a coarse assignment: both members of flow cluster c land on
    the two system nodes of its assigned system cluster ``pc[c]`` (the
    orientation is arbitrary — refinement decides it), and an odd level's
    ``pinned`` process on its node.  A permutation by construction: the
    matchings and the pinned pair partition both sides.
    """
    n = 2 * pc.shape[0] + (pinned is not None)
    p = np.empty(n, dtype=np.int32)
    p[flow_pairs[:, 0]] = sys_pairs[pc, 0]
    p[flow_pairs[:, 1]] = sys_pairs[pc, 1]
    if pinned is not None:
        p[pinned[0]] = pinned[1]
    return p


def level_order(n: int) -> int:
    """The order a level of n processes is solved at on the device: the
    next power of two, so that every order shares a few programs."""
    return 1 << max(n - 1, 0).bit_length()


def _padded(A: np.ndarray, order: int) -> np.ndarray:
    """``A`` with zero rows and columns up to ``order`` (inert: zero flows
    and distances, masked by ``n_valid``)."""
    pad = order - A.shape[0]
    return np.pad(A, ((0, pad), (0, pad))) if pad else A


def _padded_perm(p: np.ndarray, order: int) -> np.ndarray:
    """A level's permutation with the identity on the padded tail."""
    return np.concatenate([p, np.arange(p.shape[0], order)]).astype(np.int32)


def _level_flows(flows: sparse.Nonzeros, cfg: "MultilevelConfig"):
    """A refinement level's flows at its device order: dense, or ELL with
    the width of that order and the flows' density class."""
    order = level_order(flows.n)
    if cfg.refine_sa.flows != "sparse":
        return jnp.asarray(flows.dense(order))
    return sparse.from_nonzeros(flows, order,
                                sparse.ell_width(flows, order))


@contextlib.contextmanager
def _no_span(name: str, **attrs):
    yield _NoSpan()


class _NoSpan:
    def set(self, **attrs) -> None:
        pass


def _fetch(out, n: int, span: Callable):
    """Wait for a solve's permutation (first result), its order-``n``
    prefix, inside an ``engine.fetch`` span that carries the event loop's
    counts when the solve kept them (a fourth result)."""
    with span("engine.fetch") as fetch:
        p = np.asarray(out[0])[:n]
        if len(out) > 3:
            fetch.set(**annealing.loop_totals(out[3]))
    return p


def solve_multilevel(C, M, key: Optional[Array] = None,
                     cfg: Optional[MultilevelConfig] = None,
                     span: Callable = _no_span) -> MultilevelResult:
    """Coarsen → solve coarse → prolong-and-refine each level (module
    docstring).  ``C``/``M`` are dense host arrays; coarsening and the
    float64 objectives are host-side numpy on C's nonzeros, every
    solve/refine runs through the jitted solver entry points
    (sparse dispatches on the refinement levels), each level at
    ``level_order`` of its order.  The result is a permutation of the n
    processes onto the n nodes, never worse than the identity, with F in
    float64.  ``span(name, **attrs)`` is a context manager for the
    program's spans (module docstring).
    """
    cfg = cfg or MultilevelConfig()
    if cfg.algorithm not in ("psa", "pga"):
        raise ValueError(f"algorithm must be 'psa' or 'pga', got {cfg.algorithm!r}")
    key = key if key is not None else jax.random.PRNGKey(0)
    C = np.asarray(C, np.float32)
    M = np.asarray(M, np.float32)
    n = C.shape[0]

    t0 = time.perf_counter()
    # ---- coarsen: stack of (flows, M, flow_pairs, sys_pairs, pinned),
    # finest first, and each level's flows at its device order.
    with span("ml.coarsen", n=n) as coarsening:
        fine = sparse.nonzeros(C)
        stack = []
        Fl, Ml = fine, M
        while Fl.n > cfg.coarse_n and len(stack) < cfg.max_levels:
            fp, sp, pinned = match_flows(Fl, Ml)
            stack.append((Fl, Ml, fp, sp, pinned))
            Fl, Ml = contract(Fl, fp), contract_distances(Ml, sp)
        finest = _level_flows(fine, cfg)
        flows = [finest] + [_level_flows(s[0], cfg) for s in stack[1:]]
        coarsening.set(levels=len(stack), coarse_n=Fl.n, nnz=fine.nnz,
                       ell_width=finest.max_degree
                       if isinstance(finest, sparse.SparseFlows) else None)

    # ---- coarse solve (dense: at coarse_n the dense path is the fast one).
    nc = Fl.n
    order = level_order(nc)
    nv = None if order == nc else np.int32(nc)
    kc = jax.random.fold_in(key, 0)
    Cc, Mc = jnp.asarray(Fl.dense(order)), jnp.asarray(_padded(Ml, order))
    if cfg.algorithm == "psa":
        out = annealing.run_psa(
            Cc, Mc, kc, cfg.coarse_sa, cfg.num_processes, n_valid=nv,
            counts=annealing.resolved_loop(cfg.coarse_sa, order) == "event")
    else:
        out = genetic.run_pga(Cc, Mc, kc, cfg.coarse_ga, cfg.num_processes,
                              n_valid=nv)
    p = _fetch(out, nc, span)
    coarse_f = objective(Fl, Ml, p)

    # ---- prolong + warm-started refinement, coarsest to finest.
    levels = []
    keep = annealing.resolved_loop(cfg.refine_sa) == "event"
    for li, ((Fl, Ml, fp, sp, pinned), Cs) in enumerate(
            zip(reversed(stack), reversed(flows))):
        nl, nnz = Fl.n, Fl.nnz
        with span("ml.level", n=nl, nnz=nnz):
            p = prolong_perm(p, fp, sp, pinned)
            f_pro = objective(Fl, Ml, p)
            order = level_order(nl)
            out = annealing.run_psa(
                Cs, jnp.asarray(_padded(Ml, order)),
                jax.random.fold_in(key, 1 + li), cfg.refine_sa,
                cfg.num_processes,
                n_valid=None if order == nl else np.int32(nl),
                init_perm=jnp.asarray(_padded_perm(p, order)), counts=keep)
            p = _fetch(out, nl, span)
            f_ref = objective(Fl, Ml, p)
        levels.append(LevelInfo(n=nl, nnz=nnz,
                                f_prolonged=f_pro, f_refined=f_ref))

    # ---- final polish on the finest level (sparse 2-swap descent).
    if cfg.final_polish_rounds > 0:
        order = level_order(n)
        out = mapping.polish_jit(
            finest, jnp.asarray(_padded(M, order)),
            jnp.asarray(_padded_perm(p, order)), jax.random.fold_in(key, 7),
            rounds=cfg.final_polish_rounds,
            n_valid=None if order == n else np.int32(n))
        p = _fetch(out, n, span)
    f = objective(fine, M, p)
    f_identity = objective(fine, M, np.arange(n))
    if f > f_identity:          # never worse than the placement it replaces
        p, f = np.arange(n), f_identity
    return MultilevelResult(perm=p.astype(np.int32), objective=f,
                            coarse_objective=coarse_f, levels=tuple(levels),
                            seconds=time.perf_counter() - t0,
                            identity_objective=f_identity)
