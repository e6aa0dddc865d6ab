"""Parallel genetic algorithm (PGA) with ring migration.

Faithful to the paper's algorithm (S3):

  1. each process holds its own population (island model), size >= graph order;
  2. breeding: crossover (probability 1.0, "basic" order crossover) on
     tournament-selected parents;
  3. mutation with probability 0.001 per gene (swap mutation);
  4. the worst individuals are replaced by the new descendants;
  5. the best member is sent to the ring neighbour each iteration; a received
     migrant replaces the worst member only if better (paper: the number of
     migration solutions must be small -- exactly one here);
  6. after the iteration budget, the global best among processes is returned.

Representation: an individual is the permutation array ``p`` (gene i = node
assigned to process i), matching the paper's encoding.

Hardware adaptation (docs/DESIGN.md §4): offspring evaluation is the GA
cost driver (full O(N^2) objective per descendant, paper S5), so the
generation step is a **wide-generation** loop (``GAConfig.eval="wide"``,
the default): selection, OX crossover, and mutation run as flattened
(islands x n_off) batched ops, offspring fitness is **one** leading-batch
``repro.kernels.ops.qap_objective`` dispatch per generation (and one
(islands x pop) call at init) -- a single Pallas launch whose grid spans
every (island, offspring) pair on TPU, the vectorized reference on CPU --
and the worst-replacement is a tie-stable ``lax.top_k`` formulation
instead of a full ``argsort``.  Same keys + bitwise-equal operations =>
populations are **bitwise identical** to the per-island path, which is
retained verbatim as ``GAConfig(eval="island")`` and pinned as the golden
reference (tests/test_ga_hotloop.py); ``benchmarks/solver_hotloop.py ga``
tracks the island-vs-wide numbers.

Mutation fidelity note: per-gene Bernoulli(0.001) swaps are realised as a
fixed budget of ``MAX_MUT`` candidate swaps each gated with probability
``pmut * N / MAX_MUT`` -- the expected number of swaps matches the paper's
scheme while keeping the TPU program static.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ga_ops, qap, sparse
from repro.kernels import ops
from repro.kernels import prng

Array = jax.Array

MAX_MUT = ga_ops.MAX_MUT   # fixed per-individual mutation budget


@dataclass(frozen=True)
class GAConfig:
    pop_size: int = 0            # 0 => graph order (paper default)
    n_offspring: int = 0         # 0 => pop_size // 2
    p_crossover: float = 1.0
    p_mutation: float = 0.001    # per gene
    crossover: str = "ox"        # "ox" (basic) | "oxs" (with sorted parents)
    generations: int = 200
    migrants: int = 1            # paper: more than one degrades quality
    tournament: int = 2
    seed_identity: bool = False  # include the as-allocated order in the
                                 # initial population (placement use case)
    eval: str = "wide"           # "wide" | "island" | "fused" generation
                                 # realisation (bitwise-identical; "fused" =
                                 # one Pallas launch per island generation
                                 # with on-chip counter draws, auto-falling
                                 # back to "wide" above the VMEM budget — see
                                 # resolved_eval and docs/DESIGN.md §13)
    rng: str = "host"            # "host" | "counter" draw regime: "counter"
                                 # derives every operator draw from the
                                 # portable counter stream (kernels/prng.py)
                                 # the fused kernel replays on-chip —
                                 # eval="fused" implies it; "host" keeps the
                                 # original jax.random draws (the existing
                                 # goldens).  "counter" requires a wide-form
                                 # eval ("wide"/"fused")
    flows: str = "dense"         # "dense" | "sparse" flow representation:
                                 # "sparse" expects C as a
                                 # core.sparse.SparseFlows (convert host-side
                                 # via sparse.from_dense); the wide
                                 # generation's objective dispatch then runs
                                 # O(nnz) per offspring (docs/DESIGN.md §10)


class GAState(NamedTuple):
    pop: Array     # (pop_size, N) int32
    fit: Array     # (pop_size,) f32


# ----------------------------------------------------------------------------
# Genetic operators (all fully vectorised; validity property-tested).
# ----------------------------------------------------------------------------

def order_crossover(key: Array, p1: Array, p2: Array,
                    n_valid: Optional[Array] = None) -> Array:
    """OX: child keeps p1[c1:c2]; remaining positions are filled with p2's
    genes in p2-order starting at c2 (cyclically), skipping duplicates.

    Scatter-free formulation: segment membership and the rank-matched fill
    are computed with one-hot comparison matrices and gathers (XLA CPU
    scatters dominate the GA generation step otherwise).  All outputs are
    integers, so the child is **bitwise identical** to the seed-era
    scatter formulation, which is retained as
    ``_order_crossover_scatter`` (the ``eval="island"`` golden path) and
    pinned by ``tests/test_ga_hotloop.py``.

    With ``n_valid`` (instance batching) both parents must be identity on
    the padded tail; the crossover then acts on the valid prefix only and
    the child inherits the same invariant.
    """
    n = p1.shape[0]
    k1, k2 = jax.random.split(key)
    pos = jnp.arange(n)
    if n_valid is None:
        c1 = jax.random.randint(k1, (), 0, n)
        c2 = jax.random.randint(k2, (), 0, n)
        c1, c2 = jnp.minimum(c1, c2), jnp.maximum(c1, c2)

        seg_mask = (pos >= c1) & (pos < c2)              # positions from p1
        # gene_in_seg[g] = any position t in the segment with p1[t] == g
        gene_in_seg = jnp.any((p1[:, None] == pos[None, :]) &
                              seg_mask[:, None], axis=0)

        rot = (pos + c2) % n                             # fill starts at c2
        genes = p2[rot]                                  # p2 genes from c2 on
        keep = ~gene_in_seg[genes]                       # genes to place
        avail = ~seg_mask[rot]                           # positions to fill
        t_of_q = (pos - c2) % n                          # inverse of rot
        tail = None
    else:
        nv = jnp.maximum(n_valid, 1)
        c1 = jax.random.randint(k1, (), 0, nv)
        c2 = jax.random.randint(k2, (), 0, nv)
        c1, c2 = jnp.minimum(c1, c2), jnp.maximum(c1, c2)

        validp = pos < nv
        seg_mask = (pos >= c1) & (pos < c2)              # always inside prefix
        gene_in_seg = jnp.any((p1[:, None] == pos[None, :]) &
                              seg_mask[:, None], axis=0)

        # Cyclic rotation of the *valid* prefix only; padded slots map to
        # themselves so their (pad) genes are excluded below.
        rot = jnp.where(validp, (pos + c2) % nv, pos)
        genes = p2[rot]
        keep = ~gene_in_seg[genes] & validp
        avail = ~seg_mask[rot] & validp
        t_of_q = jnp.where(validp, (pos - c2) % nv, pos)
        tail = validp                                    # pad tail = identity

    # Rank matching without scatters: the r-th kept gene fills the r-th
    # available position.  val_by_rank[r] = the unique kept gene of rank r
    # (a one-hot row sum); position q (outside the segment) has rank
    # pos_rank[t_of_q] in the cyclic fill order.
    gene_rank = jnp.cumsum(keep) - 1
    pos_rank = jnp.cumsum(avail) - 1
    rankmat = (gene_rank[:, None] == pos[None, :]) & keep[:, None]
    val_by_rank = jnp.sum(jnp.where(rankmat, genes[:, None], 0), axis=0)
    r_of_q = jnp.clip(pos_rank[t_of_q], 0, n - 1)
    child = jnp.where(seg_mask, p1, val_by_rank[r_of_q])
    if tail is not None:
        child = jnp.where(tail, child, pos)
    return child.astype(p1.dtype)


def _order_crossover_scatter(key: Array, p1: Array, p2: Array,
                             n_valid: Optional[Array] = None) -> Array:
    """Seed-era OX realisation (scatter/cumsum rank matching), kept
    verbatim as the ``eval="island"`` golden reference and the old side of
    the ``benchmarks/solver_hotloop.py ga`` comparison.  Bitwise-equal to
    :func:`order_crossover` for every key (integer outputs; pinned in
    tests/test_ga_hotloop.py)."""
    n = p1.shape[0]
    k1, k2 = jax.random.split(key)
    if n_valid is None:
        c1 = jax.random.randint(k1, (), 0, n)
        c2 = jax.random.randint(k2, (), 0, n)
        c1, c2 = jnp.minimum(c1, c2), jnp.maximum(c1, c2)

        pos = jnp.arange(n)
        seg_mask = (pos >= c1) & (pos < c2)              # positions from p1
        gene_in_seg = jnp.zeros(n, jnp.bool_).at[p1].set(seg_mask)

        # Rotate so filling starts at c2 (classic OX order).
        rot = jnp.roll(pos, -c2)                         # position sequence
        genes = p2[rot]                                  # p2 genes from c2 on
        keep = ~gene_in_seg[genes]                       # genes to place
        avail = ~seg_mask[rot]                           # positions to fill
        fill = 0
    else:
        nv = jnp.maximum(n_valid, 1)
        c1 = jax.random.randint(k1, (), 0, nv)
        c2 = jax.random.randint(k2, (), 0, nv)
        c1, c2 = jnp.minimum(c1, c2), jnp.maximum(c1, c2)

        pos = jnp.arange(n)
        validp = pos < nv
        seg_mask = (pos >= c1) & (pos < c2)              # always inside prefix
        gene_in_seg = jnp.zeros(n, jnp.bool_).at[p1].set(seg_mask)

        # Cyclic rotation of the *valid* prefix only; padded slots map to
        # themselves so their (pad) genes are excluded below.
        rot = jnp.where(validp, (pos + c2) % nv, pos)
        genes = p2[rot]
        keep = ~gene_in_seg[genes] & validp
        avail = ~seg_mask[rot] & validp
        fill = jnp.where(validp, 0, pos)                 # pad tail = identity

    # rank-matched scatter: r-th kept gene -> r-th available position
    gene_rank = jnp.cumsum(keep) - 1
    pos_rank = jnp.cumsum(avail) - 1
    pos_by_rank = jnp.zeros(n, jnp.int32).at[jnp.where(avail, pos_rank, n - 1)] \
        .set(jnp.where(avail, rot, 0), mode="drop")
    child = jnp.where(seg_mask, p1, fill)
    child = child.at[jnp.where(keep, pos_by_rank[gene_rank], n)] \
        .set(jnp.where(keep, genes, 0), mode="drop")
    return child.astype(p1.dtype)


def swap_mutation(key: Array, p: Array, p_mutation: float,
                  n_valid: Optional[Array] = None) -> Array:
    """Expected p_mutation * N swap mutations via a fixed MAX_MUT budget."""
    n = p.shape[0]
    if n_valid is None:
        gate_p = jnp.minimum(p_mutation * n / MAX_MUT, 1.0)
        hi = n
    else:
        gate_p = jnp.minimum(p_mutation * n_valid / MAX_MUT, 1.0)
        hi = jnp.maximum(n_valid, 1)
    ki, kj, ku = jax.random.split(key, 3)
    ii = jax.random.randint(ki, (MAX_MUT,), 0, hi)
    jj = jax.random.randint(kj, (MAX_MUT,), 0, hi)
    us = jax.random.uniform(ku, (MAX_MUT,))

    def body(pp, t):
        i, j, u = t
        do = u < gate_p
        pi, pj = pp[i], pp[j]
        pp = pp.at[i].set(jnp.where(do, pj, pi)).at[j].set(jnp.where(do, pi, pj))
        return pp, None

    p, _ = jax.lax.scan(body, p, (ii, jj, us))
    return p


def tournament_select(key: Array, fit: Array, k: int) -> Array:
    """Index of a binary(-ish) tournament winner."""
    idx = jax.random.randint(key, (k,), 0, fit.shape[0])
    return idx[jnp.argmin(fit[idx])]


def worst_slots(fit: Array, n_off: int) -> Array:
    """Population slots of the ``n_off`` worst members, tie-stable.

    A ``lax.top_k`` formulation of ``jnp.argsort(fit)[-n_off:]`` (O(P)
    selection instead of a full O(P log P) sort per generation): the
    stable ascending argsort resolves ties toward the *higher* index at
    the cut, while ``top_k`` prefers the lower index, so the selection
    runs on the reversed array and maps back — bitwise-identical slot
    vectors, including the order (ascending fitness), for every tie
    pattern (tests/test_ga_hotloop.py).
    """
    pop = fit.shape[0]
    _, ridx = jax.lax.top_k(fit[::-1], n_off)
    return (pop - 1 - ridx)[::-1]


# ----------------------------------------------------------------------------
# Island GA
# ----------------------------------------------------------------------------

def _resolve(cfg: GAConfig, n: int) -> Tuple[int, int]:
    pop = cfg.pop_size if cfg.pop_size > 0 else n
    off = cfg.n_offspring if cfg.n_offspring > 0 else max(pop // 2, 1)
    return pop, off


def _resolve_n_off(cfg: GAConfig, pop_actual: int) -> int:
    # composite may seed pop != graph order; never breed more than pop
    n_off = cfg.n_offspring if cfg.n_offspring > 0 else max(pop_actual // 2, 1)
    return min(n_off, pop_actual)


def resolved_eval(cfg: GAConfig, n: Optional[int] = None) -> str:
    """The generation realisation that will actually run at order ``n``.

    ``"fused"`` keeps the island population, matrices, and objective
    temporaries resident in VMEM, so above the dense kernel cap
    (``ops.fused_step_fits``) — and for sparse flows — it degrades to the
    bitwise-equivalent unfused ``"wide"`` counter-mode path; nothing
    regresses at n=4096.  On a TPU backend a generation that would run
    fused raises instead: the fused kernel does not compile there
    (``ops.check_fused_backend``).
    """
    if cfg.eval not in ("wide", "island", "fused"):
        raise ValueError(f"unknown generation realisation {cfg.eval!r}")
    if cfg.eval != "fused":
        return cfg.eval
    if cfg.flows == "sparse":
        return "wide"
    if n is not None and not ops.fused_step_fits(n):
        return "wide"
    ops.check_fused_backend()
    return "fused"


def _init_population(key: Array, cfg: GAConfig, n: int,
                     n_valid: Optional[Array] = None,
                     init_perm: Optional[Array] = None) -> Array:
    """One island's initial population (permutations only, no fitness)."""
    pop_size, _ = _resolve(cfg, n)
    if n_valid is None:
        pop = qap.random_permutations(key, pop_size, n)
    else:
        pop = qap.masked_random_permutations(key, pop_size, n, n_valid)
    if cfg.seed_identity:
        pop = pop.at[0].set(jnp.arange(n, dtype=pop.dtype))
    if init_perm is not None:
        use = init_perm[0] >= 0
        seeded = jnp.where(use, init_perm.astype(pop.dtype), pop[0])
        pop = pop.at[0].set(seeded)
    return pop


def init_island(C: Array, M: Array, key: Array, cfg: GAConfig,
                n_valid: Optional[Array] = None,
                init_perm: Optional[Array] = None) -> GAState:
    """``init_perm`` (warm start) places a given feasible permutation in
    population slot 0, generalizing ``seed_identity``; a negative first
    entry is the "no warm start" sentinel and keeps the member slot 0
    already holds (random, or identity under ``seed_identity``)."""
    pop = _init_population(key, cfg, C.shape[0], n_valid, init_perm)
    fit = ops.qap_objective(C, M, pop)
    return GAState(pop=pop, fit=fit)


def _offspring(state: GAState, key: Array, cfg: GAConfig,
               n_valid: Optional[Array] = None) -> Array:
    """One island's descendants (paper steps 2-3): tournament selection,
    OX crossover, swap mutation.  Pure population/PRNG work — no
    objective evaluation — so the wide generation step can run it
    flattened over (islands x n_off) and score every island's offspring
    in a single ``ops.qap_objective`` dispatch."""
    pop_actual = state.pop.shape[0]
    n_off = _resolve_n_off(cfg, pop_actual)
    ksel, kx, kmut, kxp = jax.random.split(key, 4)

    sel_keys = jax.random.split(ksel, 2 * n_off).reshape(n_off, 2, 2)
    i1 = jax.vmap(lambda k: tournament_select(k, state.fit, cfg.tournament))(sel_keys[:, 0])
    i2 = jax.vmap(lambda k: tournament_select(k, state.fit, cfg.tournament))(sel_keys[:, 1])
    par1, par2 = state.pop[i1], state.pop[i2]
    if cfg.crossover == "oxs":
        # "crossover with sorting": the fitter parent donates the segment.
        swap = state.fit[i2] < state.fit[i1]
        par1, par2 = (jnp.where(swap[:, None], par2, par1),
                      jnp.where(swap[:, None], par1, par2))

    xkeys = jax.random.split(kx, n_off)
    do_x = jax.random.uniform(kxp, (n_off,)) < cfg.p_crossover
    children = jax.vmap(
        lambda k, a, b: order_crossover(k, a, b, n_valid))(xkeys, par1, par2)
    children = jnp.where(do_x[:, None], children, par1)

    mkeys = jax.random.split(kmut, n_off)
    children = jax.vmap(
        lambda k, p: swap_mutation(k, p, cfg.p_mutation, n_valid))(mkeys, children)
    return children


def _offspring_counter(state: GAState, key: Array, cfg: GAConfig,
                       n_valid: Optional[Array] = None) -> Array:
    """Counter-mode :func:`_offspring`: identical operator structure, but
    every draw comes from the portable counter stream of ``key``
    (``kernels/prng.py``) through the shared apply bodies
    (``core.ga_ops``) — the exact sequence the fused generation kernel
    replays on-chip, which is what makes ``eval="fused"`` bitwise-equal
    to this unfused path (tests/test_fused.py)."""
    pop_actual = state.pop.shape[0]
    n = state.pop.shape[1]
    n_off = _resolve_n_off(cfg, pop_actual)
    nv = jnp.int32(n) if n_valid is None else n_valid
    d = prng.ga_step_draws(key, n_off, cfg.tournament, ga_ops.MAX_MUT,
                           pop_actual, nv)

    i1 = jax.vmap(lambda ix: ga_ops.tournament_pick(state.fit, ix))(d.sel[:, 0])
    i2 = jax.vmap(lambda ix: ga_ops.tournament_pick(state.fit, ix))(d.sel[:, 1])
    par1, par2 = state.pop[i1], state.pop[i2]
    if cfg.crossover == "oxs":
        swap = state.fit[i2] < state.fit[i1]
        par1, par2 = (jnp.where(swap[:, None], par2, par1),
                      jnp.where(swap[:, None], par1, par2))

    children = jax.vmap(
        lambda c1, c2, a, b: ga_ops.ox_apply(c1, c2, a, b, nv))(
            d.cut1, d.cut2, par1, par2)
    children = jnp.where((d.xu < cfg.p_crossover)[:, None], children, par1)
    gate = ga_ops.mutation_gate(cfg.p_mutation, nv)
    children = jax.vmap(
        lambda p, ii, jj, uu: ga_ops.mutation_apply(p, ii, jj, uu, gate))(
            children, d.mut_i, d.mut_j, d.mut_u)
    return children


def _replace_worst(state: GAState, children: Array,
                   child_fit: Array) -> GAState:
    """Replace the worst n_off individuals with the descendants (paper
    step 4) via the tie-stable ``worst_slots`` top_k formulation, plus
    the elitism guard.
    """
    n_off = children.shape[0]
    worst = worst_slots(state.fit, n_off)
    pop = state.pop.at[worst].set(children)
    fit = state.fit.at[worst].set(child_fit)
    # Elitism guard: with n_off == pop_size every member (including the
    # best) is replaced and the island best could regress; reinstate the
    # previous best over the new worst in that case.  A bitwise no-op
    # whenever the best survived the replacement, i.e. all n_off < pop
    # configs -- and what makes the warm-start never-worse-than-seed
    # guarantee hold for every config.  (top_k(fit, 1) == argmax: both
    # take the first maximum.)
    prev_i = jnp.argmin(state.fit)
    prev_p, prev_f = state.pop[prev_i], state.fit[prev_i]
    worst_new = jax.lax.top_k(fit, 1)[1][0]
    lost = prev_f < fit.min()
    pop = pop.at[worst_new].set(jnp.where(lost, prev_p, pop[worst_new]))
    fit = fit.at[worst_new].set(jnp.where(lost, prev_f, fit[worst_new]))
    return GAState(pop=pop, fit=fit)


def breed(C: Array, M: Array, state: GAState, key: Array, cfg: GAConfig,
          n_valid: Optional[Array] = None) -> GAState:
    """One generation on one island (paper steps 2-5).

    Composition of :func:`_offspring`, one ``ops.qap_objective`` dispatch,
    and :func:`_replace_worst` — the per-island form of the wide
    generation step, used by the mesh-distributed PGA (one island per
    device, ``core.distributed``).
    """
    children = _offspring(state, key, cfg, n_valid)
    child_fit = ops.qap_objective(C, M, children)
    return _replace_worst(state, children, child_fit)


def _breed_island(C: Array, M: Array, state: GAState, key: Array,
                  cfg: GAConfig, n_valid: Optional[Array] = None) -> GAState:
    """Seed-era generation step, kept verbatim: scatter-based OX, full
    ``argsort``/``argmax`` worst-replacement, per-island objective
    dispatch.  This is the ``GAConfig(eval="island")`` golden reference
    (bitwise-equal to :func:`breed`; tests/test_ga_hotloop.py) and the
    old side of the ``benchmarks/solver_hotloop.py ga`` comparison."""
    pop_actual = state.pop.shape[0]   # composite may seed pop != graph order
    n_off = cfg.n_offspring if cfg.n_offspring > 0 else max(pop_actual // 2, 1)
    n_off = min(n_off, pop_actual)
    ksel, kx, kmut, kxp = jax.random.split(key, 4)

    sel_keys = jax.random.split(ksel, 2 * n_off).reshape(n_off, 2, 2)
    i1 = jax.vmap(lambda k: tournament_select(k, state.fit, cfg.tournament))(sel_keys[:, 0])
    i2 = jax.vmap(lambda k: tournament_select(k, state.fit, cfg.tournament))(sel_keys[:, 1])
    par1, par2 = state.pop[i1], state.pop[i2]
    if cfg.crossover == "oxs":
        # "crossover with sorting": the fitter parent donates the segment.
        swap = state.fit[i2] < state.fit[i1]
        par1, par2 = (jnp.where(swap[:, None], par2, par1),
                      jnp.where(swap[:, None], par1, par2))

    xkeys = jax.random.split(kx, n_off)
    do_x = jax.random.uniform(kxp, (n_off,)) < cfg.p_crossover
    children = jax.vmap(
        lambda k, a, b: _order_crossover_scatter(k, a, b, n_valid))(xkeys, par1, par2)
    children = jnp.where(do_x[:, None], children, par1)

    mkeys = jax.random.split(kmut, n_off)
    children = jax.vmap(
        lambda k, p: swap_mutation(k, p, cfg.p_mutation, n_valid))(mkeys, children)
    child_fit = ops.qap_objective(C, M, children)

    # Replace the worst n_off individuals with the descendants (paper step 4).
    worst = jnp.argsort(state.fit)[-n_off:]
    pop = state.pop.at[worst].set(children)
    fit = state.fit.at[worst].set(child_fit)
    # Elitism guard (see _replace_worst).
    prev_i = jnp.argmin(state.fit)
    prev_p, prev_f = state.pop[prev_i], state.fit[prev_i]
    worst_new = jnp.argmax(fit)
    lost = prev_f < fit.min()
    pop = pop.at[worst_new].set(jnp.where(lost, prev_p, pop[worst_new]))
    fit = fit.at[worst_new].set(jnp.where(lost, prev_f, fit[worst_new]))
    return GAState(pop=pop, fit=fit)


def receive_migrants(state: GAState, mig_p: Array, mig_f: Array) -> GAState:
    """Replace the worst member with the migrant if better (paper step 7)."""
    worst = jnp.argmax(state.fit)
    better = mig_f < state.fit[worst]
    pop = state.pop.at[worst].set(jnp.where(better, mig_p, state.pop[worst]))
    fit = state.fit.at[worst].set(jnp.where(better, mig_f, state.fit[worst]))
    return GAState(pop=pop, fit=fit)


def island_best(state: GAState) -> Tuple[Array, Array]:
    i = jnp.argmin(state.fit)
    return state.pop[i], state.fit[i]


def generation_step(C: Array, M: Array, state: GAState, key: Array,
                    cfg: GAConfig, num_processes: int,
                    n_valid: Optional[Array] = None
                    ) -> Tuple[GAState, Array]:
    """One multi-island generation (breeding + ring migration).

    ``cfg.eval`` picks the realisation:

    * ``"wide"`` (default): every island's selection/crossover/mutation
      runs as flattened (islands x n_off) batched ops and **one** wide
      ``ops.qap_objective`` call scores all offspring — on TPU a single
      kernel launch whose grid spans every (island, offspring) pair,
      instead of per-island kernel calls issued under ``vmap``;
    * ``"island"``: the seed-era ``vmap(_breed_island)`` path, pinned as
      the golden reference;
    * ``"fused"``: the whole per-island generation — selection through
      replacement, with operator draws derived on-chip from the counter
      stream — is **one** ``ops.qap_ga_step`` launch (degrading to the
      bitwise-equal ``"wide"`` counter path above the VMEM budget, see
      ``resolved_eval``).

    All consume the same draw streams within their rng regime and apply
    bitwise-equal operations, so the resulting populations are bitwise
    identical (tests/test_ga_hotloop.py, tests/test_fused.py).  Shared by
    ``_pga_impl`` and the composite solver's GA rounds.  Returns
    (new_state, pre-migration global best) — the history entry.
    """
    n = state.pop.shape[-1]
    ev = resolved_eval(cfg, n)
    use_counter = cfg.rng == "counter" or cfg.eval == "fused"
    keys = jax.random.split(key, num_processes)
    if ev == "fused":
        nv = jnp.int32(n) if n_valid is None else n_valid
        pop_actual = state.pop.shape[-2]
        new_pop, new_fit = ops.qap_ga_step(
            C, M, state.pop, state.fit, prng.key_data(keys),
            jnp.broadcast_to(nv, (num_processes,)),
            n_off=_resolve_n_off(cfg, pop_actual),
            tournament=cfg.tournament, p_crossover=cfg.p_crossover,
            p_mutation=cfg.p_mutation, crossover=cfg.crossover)
        state = GAState(pop=new_pop, fit=new_fit)
    elif ev == "wide":
        off_fn = _offspring_counter if use_counter else _offspring
        children = jax.vmap(
            lambda s, k: off_fn(s, k, cfg, n_valid))(state, keys)
        child_fit = ops.qap_objective(C, M, children)   # ONE wide dispatch
        state = jax.vmap(_replace_worst)(state, children, child_fit)
    else:
        state = jax.vmap(
            lambda s, k: _breed_island(C, M, s, k, cfg, n_valid))(state, keys)
    bp, bf = jax.vmap(island_best)(state)
    # Ring migration: island i receives the best of island i-1.
    mig_p, mig_f = jnp.roll(bp, 1, axis=0), jnp.roll(bf, 1, axis=0)
    state = jax.vmap(receive_migrants)(state, mig_p, mig_f)
    return state, bf.min()


def _pga_impl(C: Array, M: Array, key: Array, cfg: GAConfig,
              num_processes: int, n_valid: Optional[Array],
              init_perm: Optional[Array] = None
              ) -> Tuple[Array, Array, Array]:
    """Shared PGA body for single-instance and instance-batched paths.

    ``init_perm`` seeds slot 0 of every island; the elitism guard in the
    worst-replacement then guarantees the final best is no worse than the
    seed's objective for every config (even total-replacement ones).
    """
    if cfg.eval not in ("wide", "island", "fused"):
        raise ValueError(f"unknown generation realisation {cfg.eval!r}")
    if cfg.rng not in ("host", "counter"):
        raise ValueError(f"unknown rng regime {cfg.rng!r}")
    if cfg.rng == "counter" and cfg.eval == "island":
        raise ValueError(
            "rng='counter' requires a wide-form eval ('wide'/'fused') — "
            "eval='island' is the seed-era host-RNG golden reference")
    if cfg.flows == "sparse" and not isinstance(C, sparse.SparseFlows):
        raise TypeError(
            "GAConfig.flows='sparse' requires C as a core.sparse.SparseFlows"
            " — convert host-side with sparse.from_dense(C)")
    if n_valid is not None:
        C = qap.mask_flows(C, n_valid)
    n = C.shape[0]
    kinit, krun = jax.random.split(key)
    init_keys = jax.random.split(kinit, num_processes)
    if cfg.eval in ("wide", "fused"):
        # One (islands x pop) fitness dispatch instead of per-island calls.
        pops = jax.vmap(
            lambda k: _init_population(k, cfg, n, n_valid, init_perm))(init_keys)
        state = GAState(pop=pops, fit=ops.qap_objective(C, M, pops))
    else:
        state = jax.vmap(
            lambda k: init_island(C, M, k, cfg, n_valid, init_perm))(init_keys)

    def gen_step(st, key):
        return generation_step(C, M, st, key, cfg, num_processes, n_valid)

    gen_keys = jax.random.split(krun, cfg.generations)
    state, history = jax.lax.scan(gen_step, state, gen_keys)

    bp, bf = jax.vmap(island_best)(state)
    i = jnp.argmin(bf)
    return bp[i], bf[i], history


@functools.partial(jax.jit, static_argnames=("cfg", "num_processes"))
def run_pga(C: Array, M: Array, key: Array, cfg: GAConfig,
            num_processes: int = 4,
            n_valid: Optional[Array] = None,
            init_perm: Optional[Array] = None) -> Tuple[Array, Array, Array]:
    """Island PGA with ring exchange (single-host vmap form).

    Returns (best_perm, best_f, history) -- history[g] = global best per
    generation.  The mesh-distributed form lives in ``core.distributed``.
    ``n_valid`` restricts the search to a padded instance's valid prefix;
    ``init_perm`` warm-starts slot 0 of every island.
    """
    return _pga_impl(C, M, key, cfg, num_processes, n_valid, init_perm)


@functools.partial(jax.jit, static_argnames=("cfg", "num_processes"))
def run_pga_batch(Cs: Array, Ms: Array, keys: Array, cfg: GAConfig,
                  num_processes: int = 4,
                  n_valid: Optional[Array] = None,
                  init_perm: Optional[Array] = None
                  ) -> Tuple[Array, Array, Array]:
    """Instance-batched PGA: leading vmap axis over independent instances.

    Cs, Ms: (B, N, N); keys: (B, 2); n_valid: optional (B,); init_perm:
    optional (B, N) warm starts (negative first entry = cold).  Entry b
    equals ``run_pga(Cs[b], Ms[b], keys[b], ..., n_valid[b], init_perm[b])``.
    The wide generation step's objective dispatch folds this instance axis
    into its leading batch, so TPU waves still launch one kernel per
    generation (grid: instances x islands x offspring).
    """
    return qap.vmap_instances(
        lambda c, m, k, nv, ip: _pga_impl(c, m, k, cfg, num_processes, nv,
                                          ip),
        Cs, Ms, keys, n_valid, init_perm)
