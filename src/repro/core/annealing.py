"""Parallel simulated annealing (PSA) for the mapping problem.

Faithful to the paper's algorithm (S3):

  1. generate a starting solution (the candidate);
  2. new solution = swap of two arbitrary elements of X;
  3. accept if dF < 0, else accept with the acceptor probability exp(-dF/T);
  4. cool by the temperature-decrease function (linear ``T <- q*T`` or Cauchy
     ``T <- T / (1 + beta*T)``);
  5. stop on iteration budget / final temperature / stagnation.

Parallelism (paper S3, "several processes search for a solution; the best
found candidate is broadcast to all processes"): chains are a `vmap` batch
("solvers" within a process, Fig 5) and a process axis that is either a second
`vmap` dimension (single host) or a `shard_map` mesh axis
(``repro.core.distributed``).  Every ``iters_per_exchange`` temperature steps
the globally best solution is adopted by all chains (Fig 4).

Hardware adaptation (docs/DESIGN.md §4): at one temperature the sequential
algorithm examines up to ``max_neighbors`` candidates and accepts at most
``max_success`` of them.  Rejected candidates do not mutate the state, so
between two acceptances every candidate is scored against the *same*
permutation — the hot loop is therefore an **acceptance-event loop**
(``cfg.loop="event"``, the default): evaluate a window of the remaining
candidates' deltas in one wide batched call through
``repro.kernels.ops.qap_delta`` (vectorized reference on CPU, the Pallas
kernel on TPU), apply the first Metropolis-accepted candidate, and repeat.
On TPU the window is the whole remaining candidate set — at most
``max_success + 1`` wide rounds instead of a depth-``max_neighbors``
sequential scan; on CPU a narrower window (``resolved_event_width``)
avoids paying full re-evaluation per acceptance.  Because the candidate
stream and acceptance uniforms are identical and the window only bounds
how much is *evaluated* per round, the accept decisions — and hence the
results — are bitwise-identical for every width and equal to the
sequential candidate scan, which is retained as ``cfg.loop="scan"`` and
serves as the golden reference (tests/test_hotloop.py).

Temperature initialisation follows the UGR-Metaheuristics convention the
paper adopts: ``T0 = mu * F(s0) / -ln(phi)`` with mu = phi = 0.3, and the
Cauchy beta is ``(T0 - Tf) / (n_coolings * T0 * Tf)`` (the paper's printed
formula has the numerator sign flipped, which would heat instead of cool; we
use the standard UGR form and note the fix).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.kernels import prng

from . import qap, sparse

Array = jax.Array


@dataclass(frozen=True)
class SAConfig:
    max_neighbors: int = 50          # candidates per temperature (Figs 1-2)
    max_success: int = 10            # acceptance cap per temperature
    schedule: str = "cauchy"         # "linear" | "cauchy" (Fig 3)
    q: float = 0.95                  # linear-schedule decay factor
    mu: float = 0.3                  # T0 = mu * F(s0) / -ln(phi)
    phi: float = 0.3
    t_final: float = 1e-3
    iters_per_exchange: int = 100    # temperature steps between exchanges (Fig 4)
    num_exchanges: int = 50          # c;  total iterations = c * iters_per_exchange
    solvers: int = 125               # chains per process (Fig 5)
    seed_with: Optional[str] = None  # None | "greedy"  (initialisation variant)
    loop: str = "event"              # "event" | "scan" | "fused" hot-loop
                                     # realisation (bitwise-identical; "fused"
                                     # = one Pallas launch per temperature
                                     # step with on-chip counter draws, auto-
                                     # falling back to "event" above the VMEM
                                     # budget — see resolved_loop and
                                     # docs/DESIGN.md §13)
    rng: str = "host"                # "host" | "counter" draw regime:
                                     # "counter" derives candidate pairs and
                                     # Metropolis uniforms from the portable
                                     # counter stream (kernels/prng.py) that
                                     # the fused kernel replays on-chip —
                                     # loop="fused" implies it; "host" keeps
                                     # the original jax.random draws (the
                                     # existing goldens)
    event_width: Union[int, str, None] = None
                                     # candidates evaluated per wide round:
                                     # int | "auto" (one-shot measured
                                     # autotune, cached per (backend, n),
                                     # deterministic fallback) | None
                                     # (backend default) — see
                                     # resolved_event_width
    flows: str = "dense"             # "dense" | "sparse" flow representation:
                                     # "sparse" expects C as a
                                     # core.sparse.SparseFlows (convert once,
                                     # host-side, via sparse.from_dense) and
                                     # runs the O(nnz) delta/objective
                                     # dispatches — bitwise-equal to dense on
                                     # the integer instance families
                                     # (docs/DESIGN.md §10)


class LoopCounts(NamedTuple):
    """What the acceptance-event loop did at each temperature level of each
    lane (instance x process x solver chain): under vmap the lanes share
    one loop, which runs each level until its slowest lane is done."""
    rounds: Array   # event-loop rounds the lane ran        (..., levels)
    accepts: Array  # moves the lane accepted               (..., levels)


def loop_totals(counts: LoopCounts) -> dict:
    """A solve's loop counts, for its ``engine.fetch`` span: the rounds
    the (batched) event loop ran (at each level, as many as its slowest
    lane), the rounds its lanes ran, the lanes and the accepted moves."""
    rounds = np.asarray(counts.rounds)
    levels = rounds.reshape(-1, rounds.shape[-1])
    return {"rounds_executed": int(levels.max(axis=0).sum()),
            "lane_rounds": int(rounds.sum()), "lanes": levels.shape[0],
            "accepts": int(np.asarray(counts.accepts).sum())}


class SAState(NamedTuple):
    p: Array        # current permutation per chain        (..., N)
    f: Array        # current objective                    (...,)
    best_p: Array   # best-so-far permutation              (..., N)
    best_f: Array   # best-so-far objective                (...,)
    temp: Array     # current temperature                  (...,)


def initial_temperature(f0: Array, mu: float, phi: float) -> Array:
    return mu * f0 / -jnp.log(phi)


def cool(temp: Array, cfg: SAConfig, beta: Array) -> Array:
    if cfg.schedule == "linear":
        return temp * cfg.q
    if cfg.schedule == "cauchy":
        return temp / (1.0 + beta * temp)
    raise ValueError(f"unknown schedule {cfg.schedule!r}")


def init_chain(C: Array, M: Array, key: Array, cfg: SAConfig,
               identity: Optional[Array] = None,
               n_valid: Optional[Array] = None) -> SAState:
    """identity: when given (seed_with='identity'), this chain starts from
    the scheduler's as-allocated order instead of a random permutation --
    the greedy-initialisation variant the paper cites ([9]).

    n_valid: instance-batching support -- the chain works on a padded
    (N, N) instance whose first ``n_valid`` slots are real; the start
    permutation keeps real processes on real nodes and padded slots on
    themselves (see ``qap.masked_random_permutation``)."""
    n = C.shape[0]
    if identity is not None:
        p = identity
    elif n_valid is None:
        p = qap.random_permutation(key, n)
    else:
        p = qap.masked_random_permutation(key, n, n_valid)
    f = qap.objective(C, M, p)
    t0 = initial_temperature(f, cfg.mu, cfg.phi)
    return SAState(p=p, f=f, best_p=p, best_f=f, temp=t0)


def _candidate_scan(C: Array, M: Array, state: SAState, pairs: Array,
                    us: Array, cfg: SAConfig):
    """Golden reference hot loop (``cfg.loop="scan"``): a depth-
    ``max_neighbors`` sequential candidate scan with acceptance cap.
    Kept verbatim as the bitwise-equality oracle for the acceptance-event
    loop (tests/test_hotloop.py) and as the old side of the
    ``benchmarks/solver_hotloop.py`` comparison.  Returns the moves it
    accepted last."""
    def body(carry, inputs):
        p, f, best_p, best_f, successes = carry
        ab, u = inputs
        d = qap.swap_delta(C, M, p, ab[0], ab[1])
        accept = ((d < 0) | (u < jnp.exp(-d / jnp.maximum(state.temp, 1e-9)))) \
            & (successes < cfg.max_success)
        p_new = qap.swap_positions(p, ab[0], ab[1])
        p = jnp.where(accept, p_new, p)
        f = jnp.where(accept, f + d, f)
        better = f < best_f
        best_p = jnp.where(better, p, best_p)
        best_f = jnp.where(better, f, best_f)
        return (p, f, best_p, best_f, successes + accept.astype(jnp.int32)), None

    (p, f, best_p, best_f, successes), _ = jax.lax.scan(
        body, (state.p, state.f, state.best_p, state.best_f, jnp.int32(0)),
        (pairs, us))
    return p, f, best_p, best_f, successes


_CPU_EVENT_WIDTH = 6   # empirically balances wasted re-evaluation in the
                       # acceptance-dense (hot) phase against extra rounds
                       # in the sparse (cold) phase on the CPU backend

# event_width="auto": measured widths, cached per (backend, n).  Populated
# eagerly by autotune_event_width (mapper warmup / benchmarks); a cache
# miss during tracing falls back to the deterministic backend default so
# traced programs never depend on whether the autotune ran.
_EVENT_WIDTH_CACHE: dict = {}
_AUTO_WIDTHS = (1, 2, 4, 6, 8, 12, 16, 24, 32)
_AUTO_SUCCESSES = 5    # cost-model round counts: a temperature level runs
_AUTO_CANDIDATES = 50  # ~(successes + candidates / width) wide rounds


def _default_event_width(max_neighbors: int) -> int:
    """Deterministic backend fallback (the pre-autotune constants)."""
    if jax.default_backend() == "tpu":
        return max_neighbors
    return min(_CPU_EVENT_WIDTH, max_neighbors)


def autotune_event_width(n: int, max_neighbors: int = 50,
                         repeats: int = 3) -> int:
    """One-shot measured pick for ``SAConfig.event_width="auto"``.

    Times the jitted wide ``qap_delta`` dispatch at each candidate width
    on a synthetic order-``n`` instance and picks the width minimising
    the event-loop cost model ``(successes + candidates/width) * t(width)``
    — a temperature level pays one wide round per acceptance plus enough
    rounds to sweep the candidate list.  The result is cached per
    (backend, n); the width never changes results (only how much is
    evaluated per round), so tuning is a pure throughput knob.  Call this
    eagerly (mapper warmup, benchmarks) — inside a trace,
    :func:`resolved_event_width` only *reads* the cache.
    """
    backend = jax.default_backend()
    cached = _EVENT_WIDTH_CACHE.get((backend, n))
    if cached is not None:
        return cached
    key = jax.random.PRNGKey(0)
    kc, km, kp = jax.random.split(key, 3)
    C = jnp.round(jax.random.uniform(kc, (n, n)) * 9.0)
    M = jnp.round(jax.random.uniform(km, (n, n)) * 9.0)
    p = jnp.arange(n, dtype=jnp.int32)
    delta = jax.jit(lambda c, m, pp, prs: kernel_ops.qap_delta(c, m, pp, prs))
    best_w, best_cost = None, float("inf")
    for w in _AUTO_WIDTHS:
        pairs = qap.random_swap_pairs(kp, w, n, None)
        delta(C, M, p, pairs).block_until_ready()        # compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            delta(C, M, p, pairs).block_until_ready()
        t = (time.perf_counter() - t0) / repeats
        cost = (_AUTO_SUCCESSES + _AUTO_CANDIDATES / w) * t
        if cost < best_cost:
            best_w, best_cost = w, cost
    _EVENT_WIDTH_CACHE[(backend, n)] = best_w
    return best_w


def resolved_event_width(cfg: SAConfig, n: Optional[int] = None) -> int:
    """Candidates evaluated per wide acceptance-event round.

    ``cfg.event_width`` when set to an int; ``"auto"`` reads the
    per-(backend, n) measured cache (``autotune_event_width``) and falls
    back to the deterministic backend default on a miss, so digests and
    traced programs stay stable whether or not the autotune ran.
    Otherwise all ``max_neighbors`` candidates on TPU (one kernel launch
    covers every remaining candidate, so the sequential depth per
    temperature level is at most ``max_success + 1`` rounds) and a narrow
    ``_CPU_EVENT_WIDTH`` window on CPU, where re-evaluating the full
    candidate set every round costs more than it saves.  The width
    changes *only* how much is evaluated per round — never which
    candidates are accepted — so results are bitwise-identical for every
    width (tests/test_hotloop.py).
    """
    if cfg.event_width == "auto":
        w = _EVENT_WIDTH_CACHE.get((jax.default_backend(), n))
        if w is None:
            w = _default_event_width(cfg.max_neighbors)
        return max(1, min(w, cfg.max_neighbors))
    if cfg.event_width is not None:
        if not isinstance(cfg.event_width, int) or cfg.event_width < 1:
            raise ValueError(
                f"event_width must be >= 1 or 'auto', got {cfg.event_width!r}")
        return min(cfg.event_width, cfg.max_neighbors)
    return _default_event_width(cfg.max_neighbors)


def resolved_loop(cfg: SAConfig, n: Optional[int] = None) -> str:
    """The hot-loop realisation that will actually run at order ``n``.

    ``"fused"`` needs the whole working set (C, M, their transposes, and
    the chain state) resident in VMEM, so above the dense kernel cap
    (``kernel_ops.fused_step_fits``) — and for sparse flows, which the
    fused kernel does not stream — it degrades to the bitwise-equivalent
    unfused ``"event"`` loop; nothing regresses at n=4096.  On a TPU
    backend a loop that would run fused raises instead: the fused kernel
    does not compile there (``kernel_ops.check_fused_backend``).
    """
    if cfg.loop not in ("event", "scan", "fused"):
        raise ValueError(f"unknown hot-loop realisation {cfg.loop!r}")
    if cfg.loop != "fused":
        return cfg.loop
    if cfg.flows == "sparse":
        return "event"
    if n is not None and not kernel_ops.fused_step_fits(n):
        return "event"
    kernel_ops.check_fused_backend()
    return "fused"


def _acceptance_event_loop(C: Array, M: Array, state: SAState, pairs: Array,
                           us: Array, cfg: SAConfig, counts: bool = False):
    """Acceptance-event hot loop (``cfg.loop="event"``, the default).

    Each round scores a window of the remaining candidates against the
    current permutation in one batched ``kernels.ops.qap_delta`` dispatch
    (the whole remaining set on TPU — see ``resolved_event_width``),
    applies the first still-unconsumed Metropolis-accepted candidate, and
    advances past it; a round with no acceptance advances past its whole
    window.  Rounds stop once every candidate is consumed or
    ``max_success`` swaps landed, so the sequential depth per temperature
    level is at most ``min(max_success, K) + ceil(K / width)`` rounds —
    ``max_success + 1`` at full width — instead of ``K = max_neighbors``
    scalar steps.  Rejected candidates never mutate state, so the accept
    decisions (same candidate stream, same uniforms, same deltas bitwise
    on the CPU reference path) — and therefore the results — are
    identical to ``_candidate_scan`` for every window width.

    With ``counts`` the loop also counts its rounds and returns
    ``(rounds, accepts)`` after the state; the state is bitwise the same.
    """
    k = cfg.max_neighbors
    w = resolved_event_width(cfg, state.p.shape[0])

    def cond(carry):
        start, successes = carry[4], carry[5]
        return (start < k) & (successes < cfg.max_success)

    def body(carry):
        p, f, best_p, best_f, start, successes = carry[:6]
        # Window [off, off+w): anchored at `start`, clamped so it never
        # reads past the candidate list; rows before `start` (possible
        # only after clamping) are masked out of the accept selection.
        off = jnp.minimum(start, k - w)
        wpairs = jax.lax.dynamic_slice(pairs, (off, jnp.int32(0)), (w, 2))
        wus = jax.lax.dynamic_slice(us, (off,), (w,))
        ds = kernel_ops.qap_delta(C, M, p, wpairs)
        accept = (ds < 0) | (wus < jnp.exp(-ds / jnp.maximum(state.temp, 1e-9)))
        live = accept & (off + jnp.arange(w, dtype=jnp.int32) >= start)
        fire = live.any()
        j = jnp.argmax(live)                    # first accepted in window
        p = jnp.where(fire,
                      qap.swap_positions(p, wpairs[j, 0], wpairs[j, 1]), p)
        f = jnp.where(fire, f + ds[j], f)
        better = f < best_f
        best_p = jnp.where(better, p, best_p)
        best_f = jnp.where(better, f, best_f)
        start = jnp.where(fire, off + j + 1, off + w)
        out = (p, f, best_p, best_f, start,
               successes + fire.astype(jnp.int32))
        return out + (carry[6] + 1,) if counts else out

    init = (state.p, state.f, state.best_p, state.best_f,
            jnp.int32(0), jnp.int32(0))
    out = jax.lax.while_loop(
        cond, body, init + (jnp.int32(0),) if counts else init)
    if counts:
        return out[:4] + (out[6], out[5])
    return out[:4]


def temperature_step(C: Array, M: Array, state: SAState, key: Array,
                     cfg: SAConfig, beta: Array,
                     n_valid: Optional[Array] = None, counts: bool = False):
    """One temperature level: up to ``max_neighbors`` candidates, at most
    ``max_success`` acceptances (paper steps 2-3).

    ``cfg.loop`` picks the realisation — ``"event"`` (wide batched rounds
    through the kernel dispatch layer, the default), ``"scan"`` (the
    golden sequential reference), or ``"fused"`` (one
    ``kernels.ops.qap_sa_step`` launch for the whole level, candidate
    stream derived on-chip; degrades to ``"event"`` above the VMEM
    budget, see ``resolved_loop``); all produce bitwise-identical states
    on the CPU reference path.  ``cfg.rng`` picks the draw regime:
    ``"counter"`` (implied by ``loop="fused"``) takes candidate pairs and
    uniforms from the portable counter stream the fused kernel replays,
    ``"host"`` keeps the original ``jax.random`` draws.  With ``n_valid``
    candidate swaps stay inside the padded instance's valid prefix.

    Returns the next ``SAState``; with ``counts`` (the event loop only)
    ``(state, (rounds, accepts))``, the level's event-loop rounds and
    accepted moves."""
    if cfg.rng not in ("host", "counter"):
        raise ValueError(f"unknown rng regime {cfg.rng!r}")
    n = state.p.shape[0]
    loop = resolved_loop(cfg, n)
    if counts and loop != "event":
        raise ValueError(f"loop {loop!r} keeps no counts; only 'event' does")
    if loop == "fused":
        nv = jnp.int32(n) if n_valid is None else n_valid
        p, f, best_p, best_f = kernel_ops.qap_sa_step(
            C, M, state.p, state.f, state.best_p, state.best_f, state.temp,
            prng.key_data(key), nv, max_neighbors=cfg.max_neighbors,
            max_success=cfg.max_success,
            event_width=resolved_event_width(cfg, n))
    else:
        if cfg.rng == "counter" or cfg.loop == "fused":
            pairs, us = prng.sa_step_draws(
                key, cfg.max_neighbors,
                jnp.int32(n) if n_valid is None else n_valid)
        else:
            kpair, kacc = jax.random.split(key)
            pairs = qap.random_swap_pairs(kpair, cfg.max_neighbors, n, n_valid)
            us = jax.random.uniform(kacc, (cfg.max_neighbors,))
        if loop == "event":
            p, f, best_p, best_f, *tally = _acceptance_event_loop(
                C, M, state, pairs, us, cfg, counts)
        else:
            p, f, best_p, best_f, _ = _candidate_scan(
                C, M, state, pairs, us, cfg)
    temp = jnp.maximum(cool(state.temp, cfg, beta), cfg.t_final)
    state = SAState(p=p, f=f, best_p=best_p, best_f=best_f, temp=temp)
    return (state, tuple(tally)) if counts else state


def _adopt_best(state: SAState, best_p: Array, best_f: Array) -> SAState:
    """Paper: each process makes the broadcast best its candidate solution."""
    better = best_f < state.best_f
    return state._replace(p=best_p, f=best_f,
                          best_p=jnp.where(better[..., None], best_p, state.best_p),
                          best_f=jnp.minimum(best_f, state.best_f))


def _chain_round(C, M, state, key, cfg: SAConfig, beta,
                 n_valid: Optional[Array] = None, counts: bool = False):
    """iters_per_exchange temperature steps for one chain; with ``counts``
    also each level's ``(rounds, accepts)``, ``(iters_per_exchange,)``
    each."""
    keys = jax.random.split(key, cfg.iters_per_exchange)
    def step(s, k):
        out = temperature_step(C, M, s, k, cfg, beta, n_valid, counts)
        return out if counts else (out, None)
    state, tally = jax.lax.scan(step, state, keys)
    return (state, tally) if counts else state


def make_beta(C: Array, M: Array, key: Array, cfg: SAConfig,
              n_valid: Optional[Array] = None) -> Array:
    """Cauchy beta from T0/Tf and the total number of coolings."""
    n = C.shape[0]
    if n_valid is None:
        p0 = qap.random_permutation(key, n)
    else:
        p0 = qap.masked_random_permutation(key, n, n_valid)
    f0 = qap.objective(C, M, p0)
    t0 = initial_temperature(f0, cfg.mu, cfg.phi)
    n_cool = cfg.num_exchanges * cfg.iters_per_exchange
    return (t0 - cfg.t_final) / (n_cool * t0 * cfg.t_final)


def seed_chain0(C: Array, M: Array, init, chain_key: Array, cfg,
                num_processes: int, init_perm: Array, init_chain_fn):
    """Seed chain 0 of every process from a warm-start permutation.

    Generalizes the ``seed_with="identity"`` path: ``init_perm`` is any
    feasible permutation (e.g. a cached near-miss solution).  A negative
    first entry is the "no warm start" sentinel — the chain-0 states
    already in ``init`` are kept (random, or identity when the config's
    own seeding already ran), so a cold instance inside a warm batch
    solves bitwise-identically to a cold-only batch.
    """
    n = C.shape[0]
    use = init_perm[0] >= 0
    perm = jnp.where(use, init_perm.astype(jnp.int32),
                     jnp.arange(n, dtype=jnp.int32))
    seeded = init_chain_fn(C, M, chain_key, cfg, identity=perm)
    return jax.tree.map(
        lambda all_, one: all_.at[:, 0].set(jnp.where(
            use, jnp.broadcast_to(one, (num_processes,) + one.shape),
            all_[:, 0])),
        init, seeded)


def _psa_impl(C: Array, M: Array, key: Array, cfg: SAConfig,
              num_processes: int, exchange: bool,
              n_valid: Optional[Array],
              init_perm: Optional[Array] = None, counts: bool = False):
    """Shared PSA body for the single-instance and instance-batched paths.

    With ``n_valid`` the instance is treated as padded: flows touching
    padded slots are zeroed once up front, start permutations and candidate
    swaps stay inside the valid prefix, so the plain objective/delta remain
    exact and the returned permutation maps real processes to real nodes.

    With ``init_perm`` (warm start) chain 0 of every process starts from the
    given permutation instead of a random one, so ``best_f`` can never end
    above ``F(init_perm)`` — warm-started solves are no worse than their
    seed on any budget (see ``seed_chain0``).

    With ``cfg.flows="sparse"`` ``C`` must be a ``sparse.SparseFlows``
    (checked at trace time — conversion is host-side, so it cannot happen
    here under jit); every objective/delta then runs the sparse O(nnz)
    dispatches.  A sparse ``C`` with ``flows="dense"`` is allowed — the
    representation alone decides the dispatch path.

    With ``counts`` a fourth result holds the event loop's
    :class:`LoopCounts`, ``(num_processes, solvers, levels)`` each.
    """
    if cfg.flows == "sparse" and not isinstance(C, sparse.SparseFlows):
        raise TypeError(
            "SAConfig.flows='sparse' requires C as a core.sparse.SparseFlows"
            " — convert host-side with sparse.from_dense(C)")
    if n_valid is not None:
        C = qap.mask_flows(C, n_valid)
    kinit, kbeta, krun = jax.random.split(key, 3)
    beta = make_beta(C, M, kbeta, cfg, n_valid)

    chain_keys = jax.random.split(kinit, num_processes * cfg.solvers) \
        .reshape(num_processes, cfg.solvers, 2)
    init = jax.vmap(jax.vmap(
        lambda k: init_chain(C, M, k, cfg, n_valid=n_valid)))(chain_keys)
    if cfg.seed_with == "identity":
        # chain 0 of every process starts from the as-allocated order
        n = C.shape[0]
        ident = init_chain(C, M, chain_keys[0, 0], cfg,
                           identity=jnp.arange(n, dtype=jnp.int32))
        init = jax.tree.map(
            lambda all_, one: all_.at[:, 0].set(
                jnp.broadcast_to(one, (num_processes,) + one.shape)),
            init, ident)
    if init_perm is not None:
        # layered on top of the config's own seeding: a -1 sentinel row
        # keeps the chain-0 state the config produced (random or identity)
        init = seed_chain0(C, M, init, chain_keys[0, 0], cfg,
                           num_processes, init_perm, init_chain)

    def round_step(state, key):
        keys = jax.random.split(key, num_processes * cfg.solvers) \
            .reshape(num_processes, cfg.solvers, 2)
        out = jax.vmap(jax.vmap(
            lambda s, k: _chain_round(C, M, s, k, cfg, beta, n_valid,
                                      counts)))(state, keys)
        state, tally = out if counts else (out, None)
        gbest_f = state.best_f.min()
        flat = state.best_f.reshape(-1)
        gbest_p = state.best_p.reshape(-1, state.best_p.shape[-1])[jnp.argmin(flat)]
        if exchange:
            bp = jnp.broadcast_to(gbest_p, state.p.shape)
            bf = jnp.broadcast_to(gbest_f, state.f.shape)
            state = _adopt_best(state, bp, bf)
        return state, (gbest_f, tally) if counts else gbest_f

    round_keys = jax.random.split(krun, cfg.num_exchanges)
    state, history = jax.lax.scan(round_step, init, round_keys)

    flat_f = state.best_f.reshape(-1)
    i = jnp.argmin(flat_f)
    best_p = state.best_p.reshape(-1, state.best_p.shape[-1])[i]
    if not counts:
        return best_p, flat_f[i], history
    history, tally = history

    def per_lane(x):      # (exchanges, P, S, iters) -> (P, S, levels)
        x = jnp.moveaxis(x, 0, 2)
        return x.reshape(x.shape[:2] + (-1,))
    return best_p, flat_f[i], history, LoopCounts(*map(per_lane, tally))


@functools.partial(jax.jit, static_argnames=("cfg", "num_processes", "exchange",
                                             "counts"))
def run_psa(C: Array, M: Array, key: Array, cfg: SAConfig,
            num_processes: int = 4, exchange: bool = True,
            n_valid: Optional[Array] = None,
            init_perm: Optional[Array] = None, counts: bool = False):
    """Parallel SA on a (num_processes, solvers) chain grid (single host).

    Returns (best_perm, best_f, history) where history[r] is the global best
    objective after exchange round r.  ``n_valid`` restricts the search to a
    padded instance's valid prefix (see ``_psa_impl``); ``init_perm``
    warm-starts chain 0 of every process from a given permutation.  With
    ``counts`` (the event loop only) a fourth result holds the
    :class:`LoopCounts`; the first three are bitwise those without.
    """
    return _psa_impl(C, M, key, cfg, num_processes, exchange, n_valid,
                     init_perm, counts)


@functools.partial(jax.jit, static_argnames=("cfg", "num_processes", "exchange",
                                             "counts"))
def run_psa_batch(Cs: Array, Ms: Array, keys: Array, cfg: SAConfig,
                  num_processes: int = 4, exchange: bool = True,
                  n_valid: Optional[Array] = None,
                  init_perm: Optional[Array] = None, counts: bool = False):
    """Instance-batched PSA: a leading vmap axis over independent instances.

    Cs, Ms: (B, N, N) padded instances; keys: (B, 2) one PRNG key per
    instance; n_valid: optional (B,) valid orders (None = all full size);
    init_perm: optional (B, N) warm-start permutations (a negative first
    entry leaves that instance cold).  Returns (best_perms (B, N), best_fs
    (B,), history (B, num_exchanges)), where entry b equals
    ``run_psa(Cs[b], Ms[b], keys[b], ..., n_valid[b], init_perm[b])`` — the
    batch axis changes throughput, not results.

    With ``counts`` (the event loop only) a fourth result holds the
    :class:`LoopCounts` of every lane, ``(B, num_processes, solvers,
    num_exchanges * iters_per_exchange)`` each; the first three results
    are bitwise those of ``counts=False``.
    """
    return qap.vmap_instances(
        lambda c, m, k, nv, ip: _psa_impl(c, m, k, cfg, num_processes,
                                          exchange, nv, ip, counts),
        Cs, Ms, keys, n_valid, init_perm)
