"""The plain reference that decides ``correct``.

It imports nothing of the program.  From the machine's distance matrix,
each job's flows (both made by the benchmark) and what the resource
manager committed (nodes, permutation, reported objective), it checks:

* the allocation: ``size`` distinct in-range nodes, none held by a job
  still running at that virtual time (a replay of every commit since the
  machine was empty, each job released at its start plus its runtime);
* the permutation: a permutation of the job's processes;
* the objective: the reported F equals F recomputed here in float64
  (exact: every instance is integer-valued and the program's sums stay
  below 2**24 in float32, or are made in float64 on the host);
* the guarantee that a mapping is never worse than the identity on the
  same nodes.

``cost_ratio`` is computed here too, from the same float64 sums.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional

import numpy as np

EPS = 1e-9                      # the resource manager's virtual-clock slack


def grid_distances(dims) -> np.ndarray:
    """Rectilinear distances between all nodes of a 3-D grid, nodes in
    x-major order (float32)."""
    axes = np.meshgrid(*(np.arange(d) for d in dims), indexing="ij")
    D = np.zeros((int(np.prod(dims)),) * 2, np.float32)
    for a in axes:
        a = a.ravel().astype(np.float32)
        D += np.abs(a[:, None] - a[None, :])
    return D


def objective(C: np.ndarray, M: np.ndarray, nodes: np.ndarray,
              perm: Optional[np.ndarray] = None) -> float:
    """F = sum_kl C[k, l] * M[node(k), node(l)] in float64, where process k
    runs on ``nodes[perm[k]]`` (the identity when ``perm`` is None)."""
    phys = nodes if perm is None else nodes[perm]
    return float((C.astype(np.float64)
                  * M[np.ix_(phys, phys)].astype(np.float64)).sum())


@dataclasses.dataclass
class Commit:
    """What the resource manager committed for one job."""
    job_id: str
    size: int
    run_s: float
    clock: float                # virtual time of the commit
    nodes: np.ndarray           # sorted physical node ids
    in_window: bool = False


def allocation_faults(commits: List[Commit], num_nodes: int) -> Dict[str, str]:
    """Replay every commit in order on an empty machine; returns
    {job_id: fault} for each allocation that is malformed or overlaps a
    job still running."""
    busy = np.zeros(num_nodes, bool)
    running: list = []          # heap of (finish, seq, nodes)
    faults: Dict[str, str] = {}
    for seq, c in enumerate(commits):
        while running and running[0][0] <= c.clock + EPS:
            busy[heapq.heappop(running)[2]] = False
        nodes = np.asarray(c.nodes)
        if (nodes.shape != (c.size,) or np.unique(nodes).size != c.size
                or nodes.min() < 0 or nodes.max() >= num_nodes):
            faults[c.job_id] = f"{nodes.size} nodes for a job of {c.size}"
            continue
        if busy[nodes].any():
            faults[c.job_id] = f"{int(busy[nodes].sum())} nodes already held"
        busy[nodes] = True
        heapq.heappush(running, (c.clock + c.run_s, seq, nodes))
    return faults


@dataclasses.dataclass
class Checked:
    jobs: int = 0
    alloc_faults: int = 0
    perm_faults: int = 0
    above_identity: int = 0
    f_gap: float = 0.0          # widest |F_reported - F| / F over the jobs
    mapped_f: float = 0.0       # sum of F over the jobs
    identity_f: float = 0.0     # sum of F of the identity on the same nodes
    faults: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def cost_ratio(self) -> float:
        return self.mapped_f / self.identity_f


def check(commits: List[Commit], answers: Dict[str, tuple], flows,
          M: np.ndarray, f_gap_limit: float = 0.0) -> Checked:
    """Check every commit marked ``in_window``.  ``answers`` maps a job id
    to (perm, reported F); ``flows(job_id)`` gives the job's C.  A job
    whose F gap passes ``f_gap_limit`` is listed among the faults."""
    out = Checked()
    bad_alloc = allocation_faults(commits, M.shape[0])
    for c in commits:
        if not c.in_window:
            continue
        out.jobs += 1
        if c.job_id in bad_alloc:
            out.alloc_faults += 1
            out.faults[c.job_id] = bad_alloc[c.job_id]
            continue
        perm, reported = answers[c.job_id]
        perm = np.asarray(perm)
        if perm.shape != (c.size,) or not np.array_equal(
                np.sort(perm), np.arange(c.size)):
            out.perm_faults += 1
            out.faults[c.job_id] = "not a permutation of the job's processes"
            continue
        C = flows(c.job_id)
        f = objective(C, M, c.nodes, perm)
        f_id = objective(C, M, c.nodes)
        gap = abs(float(reported) - f) / f
        out.f_gap = max(out.f_gap, gap)
        if gap > f_gap_limit:
            out.faults[c.job_id] = f"reported F {reported} but F is {f}"
        if f > f_id:
            out.above_identity += 1
            out.faults[c.job_id] = f"F {f} above the identity's {f_id}"
        out.mapped_f += f
        out.identity_f += f_id
    return out
