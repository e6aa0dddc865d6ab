"""Pallas TPU kernel: one fused GA generation per program instance.

The wide GA path (PR 5) already scores a whole generation's offspring in
one ``qap_objective`` launch, but selection, crossover, mutation, and
replacement still run as separate XLA ops with the population round-
tripping through HBM between them, and every operator draw arrives from
host-side ``jax.random`` calls.  This kernel fuses the **entire
generation** for one island: the population and fitness vector stay in
VMEM; tournament selection, order crossover, swap mutation
(``core/ga_ops.py``), offspring evaluation (the one-hot-matmul objective
of ``qap_objective_pallas``), and tie-stable worst-replacement + elitism
all happen in one launch, with the operator draws derived on-chip from
the generation's PRNG key words (``kernels/prng.py``).

One program instance == one island; the grid is the folded leading batch
(islands x instances), so the ``custom_vmap`` fold-into-grid rules in
``ops.py`` apply unchanged.  Ring migration stays outside (it crosses
islands).  Bitwise equality against ``ref.qap_ga_step_ref`` -- and hence
the unfused ``eval="wide"`` counter-mode path -- holds on integer-valued
instances: every operator is integer arithmetic and the objective sums
are exact in f32 regardless of padding or order (docs/DESIGN.md §13).

VMEM per program: pop (P, n_pad) i32, C and M (2.25 MiB each at
n_pad = 768, double-buffered when they follow the instance: 9 MiB) and
the objective's three n_pad^2 f32 temporaries (6.75 MiB) -- about
16 MiB at the cap, v5e's whole default scoped limit.  The compiler
gives no figure of its own: Mosaic refuses the kernel's gathers and
(1, pop) blocks first, so on TPU the fused generation is refused
(``ops.check_fused_backend``) and the kernel runs in interpret mode
only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import ga_ops
from . import prng
from .mosaic import padded_order
from .qap_objective import MAX_KERNEL_N

Array = jax.Array


def _ga_step_kernel(pop_ref, fit_ref, key_ref, nv_ref, c_ref, m_ref,
                    popo_ref, fito_ref, *, n_pad: int, pop_size: int,
                    n_off: int, tournament: int, p_crossover: float,
                    p_mutation: float, crossover: str, mat_batched: bool):
    """One program instance == one island's whole generation."""
    mat = (lambda r: r[0]) if mat_batched else (lambda r: r[...])
    Cm = mat(c_ref).astype(jnp.float32)
    Mm = mat(m_ref).astype(jnp.float32)
    pop = pop_ref[0]                           # (P, n_pad) int32
    fit = fit_ref[0]                           # (P,) f32
    nv = nv_ref[0]
    d = prng.ga_draws(key_ref[0, 0], key_ref[0, 1], n_off, tournament,
                      ga_ops.MAX_MUT, pop_size, nv)
    gate = ga_ops.mutation_gate(p_mutation, nv)
    rows = jax.lax.iota(jnp.int32, pop_size)
    off_rows = jax.lax.iota(jnp.int32, n_off)

    def breed(o, carry):
        children, cfit = carry
        sel = jnp.take(d.sel, o, axis=0)       # (2, tournament)
        i1 = ga_ops.tournament_pick(fit, sel[0])
        i2 = ga_ops.tournament_pick(fit, sel[1])
        par1 = jnp.take(pop, i1, axis=0)
        par2 = jnp.take(pop, i2, axis=0)
        if crossover == "oxs":
            swap = jnp.take(fit, i2) < jnp.take(fit, i1)
            par1, par2 = (jnp.where(swap, par2, par1),
                          jnp.where(swap, par1, par2))
        child = ga_ops.ox_apply(jnp.take(d.cut1, o), jnp.take(d.cut2, o),
                                par1, par2, nv)
        do_x = jnp.take(d.xu, o) < p_crossover
        child = jnp.where(do_x, child, par1)
        child = ga_ops.mutation_apply(child, jnp.take(d.mut_i, o, axis=0),
                                      jnp.take(d.mut_j, o, axis=0),
                                      jnp.take(d.mut_u, o, axis=0), gate)
        # Offspring fitness: M[p][:, p] == P @ M @ P^T on the MXU, the
        # math of qap_objective_pallas._objective_kernel.
        onehot = (child[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (n_pad, n_pad), 1)).astype(jnp.float32)
        PM = jax.lax.dot_general(onehot, Mm, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        PMPt = jax.lax.dot_general(PM, onehot, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        cf = jnp.sum(Cm * PMPt)
        hit = off_rows == o
        children = jnp.where(hit[:, None], child[None, :], children)
        cfit = jnp.where(hit, cf, cfit)
        return children, cfit

    children, cfit = jax.lax.fori_loop(
        0, n_off, breed,
        (jnp.zeros((n_off, n_pad), jnp.int32),
         jnp.zeros((n_off,), jnp.float32)))

    # Tie-stable worst replacement: iteratively pick the worst remaining
    # slot (ties -> highest index, the top_k-on-reversed rule of
    # genetic.worst_slots) and fill it with children[n_off-1-r], which is
    # exactly pop.at[worst_slots(fit, n_off)].set(children).
    def repl(r, carry):
        new_pop, new_fit, sel_fit = carry
        m = jnp.max(sel_fit)
        j = jnp.max(jnp.where(sel_fit == m, rows, -1))
        child = jnp.take(children, n_off - 1 - r, axis=0)
        cf = jnp.take(cfit, n_off - 1 - r)
        hit = rows == j
        new_pop = jnp.where(hit[:, None], child[None, :], new_pop)
        new_fit = jnp.where(hit, cf, new_fit)
        sel_fit = jnp.where(hit, jnp.float32(-jnp.inf), sel_fit)
        return new_pop, new_fit, sel_fit

    new_pop, new_fit, _ = jax.lax.fori_loop(
        0, n_off, repl, (pop, fit, fit))

    # Elitism guard (genetic._replace_worst): if the previous best was
    # lost, it replaces the new worst member (first-max tie rule).
    mn = jnp.min(fit)
    prev_i = jnp.min(jnp.where(fit == mn, rows, pop_size))
    prev_p = jnp.take(pop, prev_i, axis=0)
    mx = jnp.max(new_fit)
    worst_new = jnp.min(jnp.where(new_fit == mx, rows, pop_size))
    lost = mn < jnp.min(new_fit)
    hit = (rows == worst_new) & lost
    new_pop = jnp.where(hit[:, None], prev_p[None, :], new_pop)
    new_fit = jnp.where(hit, mn, new_fit)

    popo_ref[0] = new_pop
    fito_ref[0] = new_fit


@functools.partial(
    jax.jit, static_argnames=("n_off", "tournament", "p_crossover",
                              "p_mutation", "crossover", "interpret"))
def qap_ga_step_pallas_batch(C: Array, M: Array, pops: Array, fits: Array,
                             keys: Array, nvs: Array, *, n_off: int,
                             tournament: int, p_crossover: float,
                             p_mutation: float, crossover: str = "ox",
                             interpret: bool = False):
    """A whole generation for B islands in one launch.

    pops: (B, P, N) island populations; fits: (B, P) f32; keys: (B, 2)
    raw uint32 key words; nvs: (B,) int32 valid orders.  C, M are either
    shared ``(N, N)`` or instance-batched ``(B0, N, N)`` with ``B0``
    dividing B (contiguous fold, as in the other kernels).  Returns
    ``(pops, fits)`` with the input shapes.
    """
    n = pops.shape[-1]
    bsz, pop_size = pops.shape[0], pops.shape[1]
    mat_batched = C.ndim == 3
    if mat_batched and (bsz % C.shape[0] != 0):
        raise ValueError(
            f"batched C/M leading dim {C.shape[0]} must divide B={bsz}")
    rpt = (bsz // C.shape[0]) if mat_batched else 1
    n_pad = padded_order(n)
    if n_pad > MAX_KERNEL_N:
        raise ValueError(f"padded N={n_pad} exceeds kernel cap {MAX_KERNEL_N}")
    pad = n_pad - n

    mat_pad = ((0, 0), (0, pad), (0, pad)) if mat_batched else \
        ((0, pad), (0, pad))
    Cp = jnp.pad(C.astype(jnp.float32), mat_pad)
    Mp = jnp.pad(M.astype(jnp.float32), mat_pad)
    tail = jnp.broadcast_to(jnp.arange(n, n_pad, dtype=jnp.int32),
                            (bsz, pop_size, pad))
    pp = jnp.concatenate([pops.astype(jnp.int32), tail], axis=2)

    if mat_batched:
        mat_spec = pl.BlockSpec((1, n_pad, n_pad), lambda i: (i // rpt, 0, 0))
    else:
        mat_spec = pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0))
    pop_spec = pl.BlockSpec((1, pop_size, n_pad), lambda i: (i, 0, 0))
    fit_spec = pl.BlockSpec((1, pop_size), lambda i: (i, 0))
    pop_out, fit_out = pl.pallas_call(
        functools.partial(_ga_step_kernel, n_pad=n_pad, pop_size=pop_size,
                          n_off=n_off, tournament=tournament,
                          p_crossover=p_crossover, p_mutation=p_mutation,
                          crossover=crossover, mat_batched=mat_batched),
        name="qap_ga_step",
        grid=(bsz,),
        in_specs=[
            pop_spec,                                      # population
            fit_spec,                                      # fitness
            pl.BlockSpec((1, 2), lambda i: (i, 0)),        # key words
            pl.BlockSpec((1,), lambda i: (i,)),            # n_valid
            mat_spec,                                      # C
            mat_spec,                                      # M
        ],
        out_specs=(pop_spec, fit_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, pop_size, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((bsz, pop_size), jnp.float32),
        ),
        interpret=interpret,
    )(pp, fits.astype(jnp.float32), keys.astype(jnp.uint32),
      nvs.astype(jnp.int32), Cp, Mp)
    return pop_out[:, :, :n], fit_out
