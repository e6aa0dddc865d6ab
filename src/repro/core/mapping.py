"""Public mapping API: the paper's technique as a framework feature.

``find_mapping`` is what the resource-manager layer (``launch/placement.py``)
calls at job-launch time: given the program graph ``C`` (traffic matrix) and
system graph ``M`` (topology distance matrix), it returns the permutation
``p`` (process/logical-device -> node/physical-device) minimising the paper's
functional (1), within a time budget set by the algorithm config.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import annealing, composite, genetic, qap, distributed

Array = jax.Array

ALGORITHMS = ("psa", "pga", "pca", "identity")


@jax.jit
def _polish_round(C: Array, M: Array, p: Array, f: Array, key: Array,
                  n_valid: Optional[Array] = None):
    """One batched 2-swap descent round: evaluate K random swaps against the
    current permutation, apply the best if it improves.  The wide delta
    evaluation goes through the same kernel dispatch as the SA hot loop
    (``qap.swap_delta_batch`` -> ``kernels.ops.qap_delta``: vectorized
    reference on CPU, Pallas kernel on TPU).  With ``n_valid`` (padded
    instances) candidate swaps stay inside the valid prefix."""
    n = p.shape[0]
    pairs = qap.random_swap_pairs(key, 256, n, n_valid)
    deltas = qap.swap_delta_batch(C, M, p, pairs)
    i = jnp.argmin(deltas)
    better = deltas[i] < -1e-9
    a, b = pairs[i, 0], pairs[i, 1]
    p_new = jnp.where(better, qap.swap_positions(p, a, b), p)
    return p_new, jnp.where(better, f + deltas[i], f)


def polish(C: Array, M: Array, p: Array, key: Array, rounds: int = 200,
           n_valid: Optional[Array] = None) -> tuple:
    """Greedy batched 2-swap local search (beyond-paper refinement, in the
    spirit of the Kernighan-Lin hybridisation the paper cites [15, 16]).

    Cheap relative to SA/GA (each round is one wide batched delta dispatch
    through ``kernels.ops``) and strictly non-increasing; applied as a
    final stage by default."""
    if n_valid is not None:
        C = qap.mask_flows(C, n_valid)
    f = qap.objective(C, M, p)

    def body(carry, k):
        pp, ff = carry
        pp, ff = _polish_round(C, M, pp, ff, k, n_valid)
        return (pp, ff), None

    (p, f), _ = jax.lax.scan(body, (p, f), jax.random.split(key, rounds))
    return p, f


# ``polish`` as one program per shape: callers that polish one instance
# at a time (the multilevel pipeline) reuse it instead of tracing the
# scan again on every call.
polish_jit = jax.jit(polish, static_argnames=("rounds",))


@functools.partial(jax.jit, static_argnames=("rounds",))
def polish_batch(Cs: Array, Ms: Array, ps: Array, keys: Array,
                 rounds: int = 200, n_valid: Optional[Array] = None) -> tuple:
    """Instance-batched ``polish``: Cs/Ms (B, N, N), ps (B, N), keys (B, 2),
    n_valid optional (B,).  Used by the serving engine so batched solves get
    the same final 2-swap refinement ``find_mapping`` applies — and, like
    the per-instance path, every round's candidate deltas route through
    the leading-batch kernel dispatch (``kernels.ops.qap_delta``)."""
    if n_valid is None:
        return jax.vmap(lambda c, m, p, k: polish(c, m, p, k, rounds)
                        )(Cs, Ms, ps, keys)
    return jax.vmap(lambda c, m, p, k, nv: polish(c, m, p, k, rounds, nv)
                    )(Cs, Ms, ps, keys, n_valid)


@dataclass
class MappingResult:
    perm: np.ndarray          # p[k] = node index for process k
    objective: float          # F(p)
    baseline: float           # F(identity) -- the un-optimised placement
    algorithm: str
    seconds: float
    history: Optional[np.ndarray] = None

    @property
    def improvement(self) -> float:
        """Relative reduction of the communication functional vs identity."""
        if self.baseline == 0:
            return 0.0
        return (self.baseline - self.objective) / self.baseline


def find_mapping(C, M, algorithm: str = "psa", *, key=None,
                 num_processes: int = 4,
                 sa_cfg: Optional[annealing.SAConfig] = None,
                 ga_cfg: Optional[genetic.GAConfig] = None,
                 polish_rounds: int = 200,
                 mesh=None, axis: str = "proc") -> MappingResult:
    """Solve the mapping problem with the selected parallel algorithm.

    With ``mesh`` given, the search itself runs distributed over the mesh
    axis (the paper's deployment: the mapping runs on the job's own nodes);
    otherwise processes are a vmap dimension on the local device.
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"algorithm must be one of {ALGORITHMS}")
    C = jnp.asarray(C, jnp.float32)
    M = jnp.asarray(M, jnp.float32)
    n = C.shape[0]
    key = key if key is not None else jax.random.PRNGKey(0)
    ident = jnp.arange(n, dtype=jnp.int32)
    baseline = float(qap.objective(C, M, ident))

    t0 = time.perf_counter()
    hist = None
    if algorithm == "identity":
        perm, f = ident, baseline
    elif algorithm == "psa":
        cfg = sa_cfg or annealing.SAConfig()
        if mesh is not None:
            perm, f, hist = distributed.run_psa_mesh(C, M, key, cfg, mesh, axis)
        else:
            perm, f, hist = annealing.run_psa(C, M, key, cfg, num_processes)
    elif algorithm == "pga":
        cfg = ga_cfg or genetic.GAConfig()
        if mesh is not None:
            perm, f, hist = distributed.run_pga_mesh(C, M, key, cfg, mesh, axis)
        else:
            perm, f, hist = genetic.run_pga(C, M, key, cfg, num_processes)
    else:  # pca
        cfg = composite.CompositeConfig(sa=sa_cfg or annealing.SAConfig(num_exchanges=10, solvers=0),
                                        ga=ga_cfg or genetic.GAConfig())
        if mesh is not None:
            perm, f, hist = distributed.run_pca_mesh(C, M, key, cfg, mesh, axis)
        else:
            perm, f, hist = composite.run_pca(C, M, key, cfg, num_processes)
    if algorithm != "identity" and polish_rounds > 0:
        perm, f = polish(C, M, perm, jax.random.fold_in(key, 7), polish_rounds)
    f = float(f)
    seconds = time.perf_counter() - t0

    # A mapping must never be worse than the trivial placement it replaces.
    if f > baseline:
        perm, f = ident, baseline
    return MappingResult(perm=np.asarray(perm), objective=f, baseline=baseline,
                         algorithm=algorithm, seconds=seconds,
                         history=None if hist is None else np.asarray(hist))
