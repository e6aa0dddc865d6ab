"""Dispatch wrappers for the QAP kernels.

On TPU backends the Pallas kernels are used; on CPU (this container) the
pure-jnp references run, with ``interpret=True`` available for kernel
validation.  Call sites in ``repro.core`` go through these wrappers only.

Both dispatches are **leading-batch aware** (``qap_objective``:
``(..., P, N) -> (..., P)``; ``qap_delta``: ``(..., N)`` x ``(..., K, 2)
-> (..., K)``), and on the kernel path they are additionally wrapped in
``jax.custom_batching.custom_vmap`` rules that fold every outer ``vmap``
axis into the kernels' explicit leading batch:

* a vmap over permutations/candidates only (chains, solvers, islands)
  joins the leading dims of one wide kernel call — the grid grows, the
  launch count does not;
* a vmap that also batches ``C``/``M`` (the batched solvers' instance
  axis) routes to the kernels' instance-batched form (``C``/``M`` of
  shape ``(B, N, N)``), again one launch.

A ``pallas_call`` therefore never reaches jax's generic vmap batching
rule.  That rule silently falls back to a *sequential per-element loop*
whenever a scalar-prefetch operand is batched (the delta kernel's case)
— the exact failure mode the wide dispatch removes; a trace-level
regression test in ``tests/test_kernels.py`` pins this for all three
batch solvers.

The sparse dispatches (``qap_objective_sparse`` / ``qap_delta_sparse``)
mirror the dense ones one-for-one — same custom-vmap fold-into-grid
rules, same shared/instance-batched split — over a
``core.sparse.SparseFlows`` pytree instead of a dense ``C``; the generic
entry points route on ``isinstance``, so every ``core`` call site gains
the sparse path without change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.sparse import SparseFlows
from . import ref
from .qap_delta import (ROW_FORM_MAX_N, qap_delta_pallas_batch,
                        qap_delta_rows_pallas_batch)
from .qap_ga_step import qap_ga_step_pallas_batch
from .mosaic import padded_order
from .qap_objective import qap_objective_pallas_batch, MAX_KERNEL_N
from .qap_sa_step import qap_sa_step_pallas_batch
from .qap_sparse import (qap_delta_sparse_pallas_batch,
                         qap_objective_sparse_pallas_batch,
                         MAX_SPARSE_KERNEL_N)

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _bcast(x: Array, batched: bool, axis_size: int) -> Array:
    """Give unbatched operands the mapped axis explicitly (leading)."""
    return x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)


def _sparse_any(sb_tree) -> bool:
    """Is any leaf of a SparseFlows-of-bools batched?  (custom_vmap hands
    pytree operands' batch flags in the operand's own structure.)"""
    return any(jax.tree_util.tree_leaves(sb_tree))


def _sparse_bcast(S: SparseFlows, sb_tree, axis_size: int) -> SparseFlows:
    """Leaf-wise :func:`_bcast` for a SparseFlows operand."""
    return jax.tree_util.tree_map(
        lambda x, bb: _bcast(x, bb, axis_size), S, sb_tree)


def _sparse_merge(S: SparseFlows) -> SparseFlows:
    """Merge the two leading axes of every leaf (vmap-over-instance-axis
    folding, the sparse analogue of ``Cs.reshape((-1,) + Cs.shape[2:])``)."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), S)


# ---------------------------------------------------------------- objective

@functools.lru_cache(maxsize=None)
def _objective_shared(interpret: bool):
    """Kernel dispatch for shared (N, N) matrices; perms (..., N) -> (...).

    The custom-vmap rule turns outer vmaps into leading batch dims (and
    hands instance-batched ``C``/``M`` to :func:`_objective_inst`), so the
    Pallas call always sees the full batch in its grid.
    """
    @jax.custom_batching.custom_vmap
    def obj(C, M, perms):
        lead = perms.shape[:-1]
        out = qap_objective_pallas_batch(
            C, M, perms.reshape((1, -1, perms.shape[-1])), interpret=interpret)
        return out.reshape(lead)

    @obj.def_vmap
    def obj_vmap(axis_size, in_batched, C, M, perms):
        cb, mb, pb = in_batched
        perms = _bcast(perms, pb, axis_size)
        if not (cb or mb):
            return obj(C, M, perms), True        # axis joins the leading dims
        return _objective_inst(interpret)(
            _bcast(C, cb, axis_size), _bcast(M, mb, axis_size), perms), True

    return obj


@functools.lru_cache(maxsize=None)
def _objective_inst(interpret: bool):
    """Instance-batched form: C, M (B, N, N); perms (B, ..., N) -> (B, ...)."""
    @jax.custom_batching.custom_vmap
    def obj_i(Cs, Ms, perms):
        b, n = Cs.shape[0], perms.shape[-1]
        lead = perms.shape[:-1]
        out = qap_objective_pallas_batch(
            Cs, Ms, perms.reshape((b, -1, n)), interpret=interpret)
        return out.reshape(lead)

    @obj_i.def_vmap
    def obj_i_vmap(axis_size, in_batched, Cs, Ms, perms):
        cb, mb, pb = in_batched
        Cs = _bcast(Cs, cb, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        perms = _bcast(perms, pb, axis_size)
        b0 = Cs.shape[1]
        out = obj_i(Cs.reshape((-1,) + Cs.shape[2:]),     # merge into the
                    Ms.reshape((-1,) + Ms.shape[2:]),     # instance axis
                    perms.reshape((-1,) + perms.shape[2:]))
        return out.reshape((axis_size, b0) + out.shape[1:]), True

    return obj_i


def qap_objective(C: Array, M: Array, perms: Array, *,
                  force_pallas: bool = False, interpret: bool = False) -> Array:
    """Leading-batch objective dispatch: F for perms (..., P, N) -> (..., P).

    One call evaluates every permutation of the batch — the GA's
    (islands x offspring) set per generation goes through here as a single
    dispatch.  On CPU the vectorized reference runs (bitwise-equal to the
    per-permutation form); on TPU one Pallas launch whose grid spans every
    (leading-dim, permutation) pair, with outer vmaps (e.g. the batched
    solvers' instance axis) folded into the grid rather than batching the
    kernel.

    A ``SparseFlows`` ``C`` routes to :func:`qap_objective_sparse`, so
    the solvers' call sites are representation-agnostic.
    """
    if isinstance(C, SparseFlows):
        return qap_objective_sparse(C, M, perms, force_pallas=force_pallas,
                                    interpret=interpret)
    n = perms.shape[-1]
    fits = padded_order(n) <= MAX_KERNEL_N
    if force_pallas or (_on_tpu() and fits):
        return _objective_shared(bool(interpret or not _on_tpu()))(C, M, perms)
    return ref.qap_objective_ref(C, M, perms)


# -------------------------------------------------------------------- delta

_DELTA_KERNELS = {"row": qap_delta_rows_pallas_batch,
                  "candidate": qap_delta_pallas_batch}


@functools.lru_cache(maxsize=None)
def _delta_shared(interpret: bool, form: str):
    """Kernel dispatch for shared matrices; (..., N) x (..., K, 2) -> (..., K)."""
    kernel = _DELTA_KERNELS[form]

    @jax.custom_batching.custom_vmap
    def delta(C, M, p, pairs):
        n, k = p.shape[-1], pairs.shape[-2]
        lead = p.shape[:-1]
        out = kernel(C, M, p.reshape((-1, n)), pairs.reshape((-1, k, 2)),
                     interpret=interpret)
        return out.reshape(lead + (k,))

    @delta.def_vmap
    def delta_vmap(axis_size, in_batched, C, M, p, pairs):
        cb, mb, pb, rb = in_batched
        p = _bcast(p, pb, axis_size)
        pairs = _bcast(pairs, rb, axis_size)
        if not (cb or mb):
            return delta(C, M, p, pairs), True
        return _delta_inst(interpret, form)(
            _bcast(C, cb, axis_size), _bcast(M, mb, axis_size), p, pairs), True

    return delta


@functools.lru_cache(maxsize=None)
def _delta_inst(interpret: bool, form: str):
    """Instance-batched form: C, M (B, N, N); p (B, ..., N) -> (B, ..., K)."""
    kernel = _DELTA_KERNELS[form]

    @jax.custom_batching.custom_vmap
    def delta_i(Cs, Ms, p, pairs):
        n, k = p.shape[-1], pairs.shape[-2]
        lead = p.shape[:-1]
        out = kernel(Cs, Ms, p.reshape((-1, n)), pairs.reshape((-1, k, 2)),
                     interpret=interpret)
        return out.reshape(lead + (k,))

    @delta_i.def_vmap
    def delta_i_vmap(axis_size, in_batched, Cs, Ms, p, pairs):
        cb, mb, pb, rb = in_batched
        Cs = _bcast(Cs, cb, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        p = _bcast(p, pb, axis_size)
        pairs = _bcast(pairs, rb, axis_size)
        b0 = Cs.shape[1]
        out = delta_i(Cs.reshape((-1,) + Cs.shape[2:]),
                      Ms.reshape((-1,) + Ms.shape[2:]),
                      p.reshape((-1,) + p.shape[2:]),
                      pairs.reshape((-1,) + pairs.shape[2:]))
        return out.reshape((axis_size, b0) + out.shape[1:]), True

    return delta_i


def qap_delta(C: Array, M: Array, p: Array, pairs: Array, *,
              force_pallas: bool = False, interpret: bool = False) -> Array:
    """Leading-batch-aware batched swap deltas.

    ``p``: (..., N) permutations; ``pairs``: (..., K, 2) candidate swaps
    with leading dims matching ``p``  ->  (..., K) deltas.  This is the
    SA hot loop's wide evaluation surface (``annealing.temperature_step``
    scores all remaining candidates of a temperature level in one call):
    on CPU it runs the vectorized reference (bitwise-equal per candidate
    to ``core.qap.swap_delta``), on TPU one Pallas launch of the form
    :func:`delta_form` picks, with outer vmaps (chains, solvers,
    instances) folded into its grid.  ``force_pallas`` runs the kernel
    form the order would take on TPU.

    A ``SparseFlows`` ``C`` routes to :func:`qap_delta_sparse`, so the
    solvers' call sites are representation-agnostic.
    """
    if isinstance(C, SparseFlows):
        return qap_delta_sparse(C, M, p, pairs, force_pallas=force_pallas,
                                interpret=interpret)
    n = p.shape[-1]
    form = _kernel_form(n) if force_pallas else delta_form(n)
    if form == "reference":
        return ref.qap_delta_ref(C, M, p, pairs)
    return _delta_shared(bool(interpret or not _on_tpu()), form)(
        C, M, p, pairs)


def _kernel_form(n: int) -> str:
    """The delta kernel's form at order ``n``: the row form up to its
    VMEM cap, the per-candidate form above it."""
    return "row" if padded_order(n) <= ROW_FORM_MAX_N else "candidate"


def delta_form(n: int) -> str:
    """What :func:`qap_delta` runs for dense order-``n`` instances:
    ``"row"`` (one grid step per permutation row, the matrices resident
    in VMEM) where the padded order fits ``ROW_FORM_MAX_N``,
    ``"candidate"`` (one grid step per candidate, rows streamed) up to
    ``MAX_KERNEL_N``, and ``"reference"`` (the jnp formula) above that
    or off TPU."""
    if not _on_tpu() or padded_order(n) > MAX_KERNEL_N:
        return "reference"
    return _kernel_form(n)


def delta_order(n: int) -> int:
    """The order :func:`qap_delta` works at for dense order-``n``
    instances: the kernel's lane-padded order where it takes a kernel
    form, ``n`` on the reference path."""
    return n if delta_form(n) == "reference" else padded_order(n)


def sparse_delta_order(n: int) -> int:
    """The order :func:`qap_delta_sparse` works at for order-``n``
    instances: the kernel's lane-padded order on a TPU up to
    ``MAX_SPARSE_KERNEL_N``, ``n`` on the reference path."""
    if _on_tpu() and padded_order(n) <= MAX_SPARSE_KERNEL_N:
        return padded_order(n)
    return n


# --------------------------------------------------------- fused solver steps

def fused_step_fits(n: int) -> bool:
    """Does the fused solver-step working set fit VMEM at order ``n``?

    The fused SA/GA step kernels keep full matrices (and, for GA, the
    island population and objective temporaries) resident per program, so
    they share the dense objective kernel's padded-order cap.  Above it
    ``annealing.resolved_loop`` / ``genetic.resolved_eval`` fall back to
    the unfused event/wide paths — nothing regresses at n=4096.
    """
    return padded_order(n) <= MAX_KERNEL_N


FUSED_ON_TPU_ERROR = (
    "the fused SA/GA step kernels (kernels/qap_sa_step.py, qap_ga_step.py) "
    "do not compile for TPU: Mosaic refuses their in-kernel gathers "
    "(jnp.take on vectors and on rows of values: 'Only 2D gather is "
    "supported') and their (1, n_pad) / (1,) blocks break the (8, 128) "
    "block tiling rule.  Use SAConfig(loop='event') / GAConfig(eval='wide'),"
    " which run the served Pallas kernels.")


def check_fused_backend() -> None:
    """Refuse the fused solver steps on TPU, where they do not compile
    (:data:`FUSED_ON_TPU_ERROR`); they stay available on CPU, where the
    lock-step references and interpret-mode kernels run."""
    if _on_tpu():
        raise NotImplementedError(FUSED_ON_TPU_ERROR)


@functools.lru_cache(maxsize=None)
def _sa_step_shared(interpret: bool, max_neighbors: int, max_success: int):
    """Fused-SA-step dispatch for shared matrices.

    State operands carry matching leading dims (chains, solvers, ...);
    the custom-vmap rule folds every outer vmap axis into the kernel
    grid, handing instance-batched ``C``/``M`` to :func:`_sa_step_inst`.
    """
    @jax.custom_batching.custom_vmap
    def step(C, M, p, f, bp, bf, temp, key, nv):
        n = p.shape[-1]
        lead = p.shape[:-1]
        po, fo, bpo, bfo = qap_sa_step_pallas_batch(
            C, M, p.reshape((-1, n)), f.reshape((-1,)),
            bp.reshape((-1, n)), bf.reshape((-1,)), temp.reshape((-1,)),
            key.reshape((-1, 2)), nv.reshape((-1,)),
            max_neighbors=max_neighbors, max_success=max_success,
            interpret=interpret)
        return (po.reshape(lead + (n,)), fo.reshape(lead),
                bpo.reshape(lead + (n,)), bfo.reshape(lead))

    @step.def_vmap
    def step_vmap(axis_size, in_batched, C, M, *state):
        cb, mb = in_batched[0], in_batched[1]
        state = [_bcast(x, b, axis_size)
                 for x, b in zip(state, in_batched[2:])]
        if not (cb or mb):
            return step(C, M, *state), (True, True, True, True)
        return _sa_step_inst(interpret, max_neighbors, max_success)(
            _bcast(C, cb, axis_size), _bcast(M, mb, axis_size),
            *state), (True, True, True, True)

    return step


@functools.lru_cache(maxsize=None)
def _sa_step_inst(interpret: bool, max_neighbors: int, max_success: int):
    """Instance-batched fused SA step: C, M (B, N, N); state (B, ...)."""
    @jax.custom_batching.custom_vmap
    def step_i(Cs, Ms, p, f, bp, bf, temp, key, nv):
        n = p.shape[-1]
        lead = p.shape[:-1]
        # Rows of one instance are contiguous in the flattened batch —
        # the kernel's i // rpt matrix indexing contract.
        po, fo, bpo, bfo = qap_sa_step_pallas_batch(
            Cs, Ms, p.reshape((-1, n)), f.reshape((-1,)),
            bp.reshape((-1, n)), bf.reshape((-1,)), temp.reshape((-1,)),
            key.reshape((-1, 2)), nv.reshape((-1,)),
            max_neighbors=max_neighbors, max_success=max_success,
            interpret=interpret)
        return (po.reshape(lead + (n,)), fo.reshape(lead),
                bpo.reshape(lead + (n,)), bfo.reshape(lead))

    @step_i.def_vmap
    def step_i_vmap(axis_size, in_batched, Cs, Ms, *state):
        cb, mb = in_batched[0], in_batched[1]
        Cs = _bcast(Cs, cb, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        state = [_bcast(x, b, axis_size)
                 for x, b in zip(state, in_batched[2:])]
        b0 = Cs.shape[1]
        outs = step_i(Cs.reshape((-1,) + Cs.shape[2:]),
                      Ms.reshape((-1,) + Ms.shape[2:]),
                      *[x.reshape((-1,) + x.shape[2:]) for x in state])
        return tuple(o.reshape((axis_size, b0) + o.shape[1:])
                     for o in outs), (True, True, True, True)

    return step_i


def qap_sa_step(C: Array, M: Array, p: Array, f: Array, best_p: Array,
                best_f: Array, temp: Array, key: Array, n_valid: Array, *,
                max_neighbors: int, max_success: int, event_width=None,
                force_pallas: bool = False, interpret: bool = False):
    """One whole SA temperature step, fused: ``(p, f, best_p, best_f)``.

    ``p``/``best_p``: (..., N); ``f``/``best_f``/``temp``/``n_valid``:
    (...); ``key``: (..., 2) raw uint32 key words (``prng.key_data``) —
    candidate pairs and Metropolis uniforms are derived on-chip from the
    counter stream, not passed in.  On CPU the event-window reference
    runs (bitwise-equal to the unfused ``loop="event"``/``"scan"``
    counter-mode paths; ``event_width`` only shapes its windows, never
    its results); on TPU one Pallas launch per step with outer vmaps
    folded into the grid.  Callers guard orders with
    :func:`fused_step_fits` (``annealing.resolved_loop``).
    """
    if not (force_pallas or _on_tpu()):
        return ref.qap_sa_step_ref(
            C, M, p, f, best_p, best_f, temp, key, n_valid,
            max_neighbors=max_neighbors, max_success=max_success,
            event_width=event_width)
    return _sa_step_shared(bool(interpret or not _on_tpu()),
                           int(max_neighbors), int(max_success))(
        C, M, p, f, best_p, best_f, temp, key, n_valid)


@functools.lru_cache(maxsize=None)
def _ga_step_shared(interpret: bool, n_off: int, tournament: int,
                    p_crossover: float, p_mutation: float, crossover: str):
    """Fused-GA-generation dispatch for shared matrices."""
    @jax.custom_batching.custom_vmap
    def step(C, M, pop, fit, key, nv):
        psz, n = pop.shape[-2], pop.shape[-1]
        lead = pop.shape[:-2]
        po, fo = qap_ga_step_pallas_batch(
            C, M, pop.reshape((-1, psz, n)), fit.reshape((-1, psz)),
            key.reshape((-1, 2)), nv.reshape((-1,)), n_off=n_off,
            tournament=tournament, p_crossover=p_crossover,
            p_mutation=p_mutation, crossover=crossover, interpret=interpret)
        return po.reshape(lead + (psz, n)), fo.reshape(lead + (psz,))

    @step.def_vmap
    def step_vmap(axis_size, in_batched, C, M, *state):
        cb, mb = in_batched[0], in_batched[1]
        state = [_bcast(x, b, axis_size)
                 for x, b in zip(state, in_batched[2:])]
        if not (cb or mb):
            return step(C, M, *state), (True, True)
        return _ga_step_inst(interpret, n_off, tournament, p_crossover,
                             p_mutation, crossover)(
            _bcast(C, cb, axis_size), _bcast(M, mb, axis_size),
            *state), (True, True)

    return step


@functools.lru_cache(maxsize=None)
def _ga_step_inst(interpret: bool, n_off: int, tournament: int,
                  p_crossover: float, p_mutation: float, crossover: str):
    """Instance-batched fused GA generation: C, M (B, N, N)."""
    @jax.custom_batching.custom_vmap
    def step_i(Cs, Ms, pop, fit, key, nv):
        psz, n = pop.shape[-2], pop.shape[-1]
        lead = pop.shape[:-2]
        po, fo = qap_ga_step_pallas_batch(
            Cs, Ms, pop.reshape((-1, psz, n)), fit.reshape((-1, psz)),
            key.reshape((-1, 2)), nv.reshape((-1,)), n_off=n_off,
            tournament=tournament, p_crossover=p_crossover,
            p_mutation=p_mutation, crossover=crossover, interpret=interpret)
        return po.reshape(lead + (psz, n)), fo.reshape(lead + (psz,))

    @step_i.def_vmap
    def step_i_vmap(axis_size, in_batched, Cs, Ms, *state):
        cb, mb = in_batched[0], in_batched[1]
        Cs = _bcast(Cs, cb, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        state = [_bcast(x, b, axis_size)
                 for x, b in zip(state, in_batched[2:])]
        b0 = Cs.shape[1]
        outs = step_i(Cs.reshape((-1,) + Cs.shape[2:]),
                      Ms.reshape((-1,) + Ms.shape[2:]),
                      *[x.reshape((-1,) + x.shape[2:]) for x in state])
        return tuple(o.reshape((axis_size, b0) + o.shape[1:])
                     for o in outs), (True, True)

    return step_i


def qap_ga_step(C: Array, M: Array, pop: Array, fit: Array, key: Array,
                n_valid: Array, *, n_off: int, tournament: int,
                p_crossover: float, p_mutation: float,
                crossover: str = "ox", force_pallas: bool = False,
                interpret: bool = False):
    """One whole GA generation for an island, fused: ``(pop, fit)``.

    ``pop``: (..., P, N); ``fit``: (..., P); ``key``: (..., 2) raw uint32
    key words; ``n_valid``: (...).  Selection, crossover, mutation,
    offspring evaluation, and replacement run in one launch with the
    operator draws derived on-chip (``kernels/prng.py``); ring migration
    stays with the caller.  On CPU the reference runs (bitwise-equal to
    the unfused ``eval="wide"`` counter-mode path); on TPU outer vmaps
    fold into the kernel grid.  Callers guard orders with
    :func:`fused_step_fits` (``genetic.resolved_eval``).
    """
    if not (force_pallas or _on_tpu()):
        return ref.qap_ga_step_ref(
            C, M, pop, fit, key, n_valid, n_off=n_off,
            tournament=tournament, p_crossover=p_crossover,
            p_mutation=p_mutation, crossover=crossover)
    return _ga_step_shared(bool(interpret or not _on_tpu()), int(n_off),
                           int(tournament), float(p_crossover),
                           float(p_mutation), str(crossover))(
        C, M, pop, fit, key, n_valid)


# ---------------------------------------------------------------- sparse

@functools.lru_cache(maxsize=None)
def _sparse_objective_shared(interpret: bool):
    """Sparse kernel dispatch for shared flows; perms (..., N) -> (...)."""
    @jax.custom_batching.custom_vmap
    def obj(S, M, perms):
        lead = perms.shape[:-1]
        out = qap_objective_sparse_pallas_batch(
            S, M, perms.reshape((1, -1, perms.shape[-1])), interpret=interpret)
        return out.reshape(lead)

    @obj.def_vmap
    def obj_vmap(axis_size, in_batched, S, M, perms):
        sb_tree, mb, pb = in_batched
        perms = _bcast(perms, pb, axis_size)
        if not (_sparse_any(sb_tree) or mb):
            return obj(S, M, perms), True        # axis joins the leading dims
        return _sparse_objective_inst(interpret)(
            _sparse_bcast(S, sb_tree, axis_size),
            _bcast(M, mb, axis_size), perms), True

    return obj


@functools.lru_cache(maxsize=None)
def _sparse_objective_inst(interpret: bool):
    """Instance-batched sparse form: S leaves/M carry (B, ...) leading."""
    @jax.custom_batching.custom_vmap
    def obj_i(S, Ms, perms):
        b, n = Ms.shape[0], perms.shape[-1]
        lead = perms.shape[:-1]
        out = qap_objective_sparse_pallas_batch(
            S, Ms, perms.reshape((b, -1, n)), interpret=interpret)
        return out.reshape(lead)

    @obj_i.def_vmap
    def obj_i_vmap(axis_size, in_batched, S, Ms, perms):
        sb_tree, mb, pb = in_batched
        S = _sparse_bcast(S, sb_tree, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        perms = _bcast(perms, pb, axis_size)
        b0 = Ms.shape[1]
        out = obj_i(_sparse_merge(S),
                    Ms.reshape((-1,) + Ms.shape[2:]),
                    perms.reshape((-1,) + perms.shape[2:]))
        return out.reshape((axis_size, b0) + out.shape[1:]), True

    return obj_i


def qap_objective_sparse(S: SparseFlows, M: Array, perms: Array, *,
                         force_pallas: bool = False,
                         interpret: bool = False) -> Array:
    """Sparse leading-batch objective dispatch — O(nnz) per permutation.

    Same contract as :func:`qap_objective` with ``C`` replaced by a
    ``core.sparse.SparseFlows``: perms (..., P, N) -> (..., P), CPU runs
    the vectorized sparse reference (bitwise-equal to the dense dispatch
    on integer-valued instances), TPU one row-streaming Pallas launch
    with outer vmaps folded into the grid.  The kernel keeps only M
    *rows* resident, so the size ceiling is ``MAX_SPARSE_KERNEL_N``
    (4096), not the dense ``MAX_KERNEL_N``.
    """
    n = perms.shape[-1]
    fits = padded_order(n) <= MAX_SPARSE_KERNEL_N
    if force_pallas or (_on_tpu() and fits):
        return _sparse_objective_shared(
            bool(interpret or not _on_tpu()))(S, M, perms)
    return ref.qap_objective_sparse_ref(S, M, perms)


@functools.lru_cache(maxsize=None)
def _sparse_delta_shared(interpret: bool):
    """Sparse delta dispatch for shared flows; (..., N) x (..., K, 2)."""
    @jax.custom_batching.custom_vmap
    def delta(S, M, p, pairs):
        n, k = p.shape[-1], pairs.shape[-2]
        lead = p.shape[:-1]
        out = qap_delta_sparse_pallas_batch(
            S, M, p.reshape((-1, n)), pairs.reshape((-1, k, 2)),
            interpret=interpret)
        return out.reshape(lead + (k,))

    @delta.def_vmap
    def delta_vmap(axis_size, in_batched, S, M, p, pairs):
        sb_tree, mb, pb, rb = in_batched
        p = _bcast(p, pb, axis_size)
        pairs = _bcast(pairs, rb, axis_size)
        if not (_sparse_any(sb_tree) or mb):
            return delta(S, M, p, pairs), True
        return _sparse_delta_inst(interpret)(
            _sparse_bcast(S, sb_tree, axis_size),
            _bcast(M, mb, axis_size), p, pairs), True

    return delta


@functools.lru_cache(maxsize=None)
def _sparse_delta_inst(interpret: bool):
    """Instance-batched sparse delta form (S leaves/M lead with B)."""
    @jax.custom_batching.custom_vmap
    def delta_i(S, Ms, p, pairs):
        n, k = p.shape[-1], pairs.shape[-2]
        lead = p.shape[:-1]
        out = qap_delta_sparse_pallas_batch(
            S, Ms, p.reshape((-1, n)), pairs.reshape((-1, k, 2)),
            interpret=interpret)
        return out.reshape(lead + (k,))

    @delta_i.def_vmap
    def delta_i_vmap(axis_size, in_batched, S, Ms, p, pairs):
        sb_tree, mb, pb, rb = in_batched
        S = _sparse_bcast(S, sb_tree, axis_size)
        Ms = _bcast(Ms, mb, axis_size)
        p = _bcast(p, pb, axis_size)
        pairs = _bcast(pairs, rb, axis_size)
        b0 = Ms.shape[1]
        out = delta_i(_sparse_merge(S),
                      Ms.reshape((-1,) + Ms.shape[2:]),
                      p.reshape((-1,) + p.shape[2:]),
                      pairs.reshape((-1,) + pairs.shape[2:]))
        return out.reshape((axis_size, b0) + out.shape[1:]), True

    return delta_i


def qap_delta_sparse(S: SparseFlows, M: Array, p: Array, pairs: Array, *,
                     force_pallas: bool = False,
                     interpret: bool = False) -> Array:
    """Sparse leading-batch swap deltas — O(max_degree) per candidate.

    Same contract as :func:`qap_delta` over a SparseFlows: the SA
    acceptance-event loop's wide candidate evaluation goes through here
    when ``SAConfig.flows="sparse"``.  CPU runs the sparse reference
    (bitwise-equal to the dense dispatch on integer-valued instances);
    TPU one Pallas launch streaming four sparse rows + four M rows per
    candidate.
    """
    fits = padded_order(p.shape[-1]) <= MAX_SPARSE_KERNEL_N
    if force_pallas or (_on_tpu() and fits):
        return _sparse_delta_shared(
            bool(interpret or not _on_tpu()))(S, M, p, pairs)
    return ref.qap_delta_sparse_ref(S, M, p, pairs)
