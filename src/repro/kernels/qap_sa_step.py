"""Pallas TPU kernel: one fused SA temperature step per program instance.

PRs 4-5 made the SA inner loop *wide* -- one ``qap_delta`` launch scores a
whole acceptance-event window -- but every window still round-trips the
permutation, objective, and best-so-far state through XLA/HBM, and the
candidate pairs + Metropolis uniforms arrive as host-precomputed arrays.
This kernel fuses the **entire temperature step**: state lives in VMEM
across all ``max_neighbors`` candidates, and the candidate stream is
derived on-chip from the step's PRNG key words via the portable counter
stream (``kernels/prng.py``), so one launch replaces the whole
per-temperature dispatch sequence (docs/DESIGN.md §13).

One program instance == one SA chain; the grid is the folded leading
batch (chains x solvers x instances), exactly like ``qap_objective`` /
``qap_delta``, so the ``custom_vmap`` fold-into-grid rules in ``ops.py``
apply unchanged and the engine/sharded/composite/fleet paths inherit the
fused step for free.

The candidate loop inside the kernel is the sequential Metropolis scan of
``annealing._candidate_scan`` with the O(N) swap-delta of
``qap_delta_pallas`` inlined (full C/M/C^T/M^T resident per program).  Rejected
candidates never mutate state, so this is bitwise-equal to the
acceptance-event window loop for any window width; equality against
``ref.qap_sa_step_ref`` (and hence the unfused counter-mode host paths)
is exact on integer-valued instances, where every f32 sum is exact in any
summation order (docs/DESIGN.md §13).

VMEM per program: the four n_pad^2 f32 matrices, 2.25 MiB each at
n_pad = 768 and double-buffered when they follow the instance -- 18 MiB
before any temporary, above v5e's 16 MiB default scoped limit.  The
compiler gives no figure of its own: Mosaic refuses the kernel's
in-kernel gathers and (1, n_pad) blocks first, so on TPU the fused loop
is refused (``ops.check_fused_backend``) and the kernel runs in
interpret mode only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import prng
from .mosaic import padded_order
from .qap_objective import MAX_KERNEL_N

Array = jax.Array


def _sa_step_kernel(p_ref, f_ref, bp_ref, bf_ref, temp_ref, key_ref, nv_ref,
                    c_ref, ct_ref, m_ref, mt_ref,
                    po_ref, fo_ref, bpo_ref, bfo_ref, *,
                    n_pad: int, max_neighbors: int, max_success: int,
                    mat_batched: bool):
    """One program instance == one chain's whole temperature step."""
    mat = (lambda r: r[0]) if mat_batched else (lambda r: r[...])
    Cm = mat(c_ref).astype(jnp.float32)       # (n_pad, n_pad)
    CmT = mat(ct_ref).astype(jnp.float32)     # C^T (columns as rows)
    Mm = mat(m_ref).astype(jnp.float32)
    MmT = mat(mt_ref).astype(jnp.float32)
    p0 = p_ref[0, :]                          # (n_pad,) int32
    f0 = f_ref[0]
    bp0 = bp_ref[0, :]
    bf0 = bf_ref[0]
    temp = temp_ref[0]
    nv = nv_ref[0]

    # On-chip candidate stream: the whole step's swap pairs + Metropolis
    # uniforms from the key's counter stream -- no host arrays.
    a, b, us = prng.sa_draws(key_ref[0, 0], key_ref[0, 1], max_neighbors, nv)
    idx = jax.lax.iota(jnp.int32, n_pad)
    tsafe = jnp.maximum(temp, 1e-9)

    def body(t, carry):
        p, f, bp, bf, successes = carry
        aa = jnp.take(a, t)
        bb = jnp.take(b, t)
        u = jnp.take(us, t)
        uu = jnp.take(p, aa)                  # node currently at position a
        vv = jnp.take(p, bb)

        # O(N) swap delta: the col/row/corner decomposition of
        # qap_delta_pallas._delta_kernel against the resident matrices.
        ca = jnp.take(Cm, aa, axis=0)         # C[a, :]
        cb = jnp.take(Cm, bb, axis=0)
        cta = jnp.take(CmT, aa, axis=0)       # C[:, a]
        ctb = jnp.take(CmT, bb, axis=0)
        mu = jnp.take(Mm, uu, axis=0)         # M[u, :]
        mv = jnp.take(Mm, vv, axis=0)
        mtu = jnp.take(MmT, uu, axis=0)       # M[:, u]
        mtv = jnp.take(MmT, vv, axis=0)
        m_p_v = jnp.take(mtv, p)              # M[p, v]
        m_p_u = jnp.take(mtu, p)
        m_v_p = jnp.take(mv, p)               # M[v, p]
        m_u_p = jnp.take(mu, p)
        mask = (idx != aa) & (idx != bb)
        col = jnp.where(mask, (cta - ctb) * (m_p_v - m_p_u), 0.0).sum()
        row = jnp.where(mask, (ca - cb) * (m_v_p - m_u_p), 0.0).sum()
        corner = ((jnp.take(cta, aa) - jnp.take(ctb, bb))
                  * (jnp.take(m_p_v, bb) - jnp.take(m_p_u, aa))
                  + jnp.take(ca, bb)
                  * (jnp.take(m_p_u, bb) - jnp.take(m_p_v, aa))
                  + jnp.take(cb, aa)
                  * (jnp.take(m_p_v, aa) - jnp.take(m_p_u, bb)))
        d = col + row + corner

        # Metropolis acceptance + best-so-far tracking: the arithmetic of
        # annealing._candidate_scan, with the swap in select form.
        accept = (((d < 0) | (u < jnp.exp(-d / tsafe)))
                  & (successes < max_success))
        swapped = jnp.where(idx == aa, vv, jnp.where(idx == bb, uu, p))
        p = jnp.where(accept, swapped, p)
        f = jnp.where(accept, f + d, f)
        better = f < bf
        bp = jnp.where(better, p, bp)
        bf = jnp.where(better, f, bf)
        return (p, f, bp, bf, successes + accept.astype(jnp.int32))

    p, f, bp, bf, _ = jax.lax.fori_loop(
        0, max_neighbors, body, (p0, f0, bp0, bf0, jnp.int32(0)))
    po_ref[0, :] = p
    fo_ref[0] = f
    bpo_ref[0, :] = bp
    bfo_ref[0] = bf


@functools.partial(
    jax.jit, static_argnames=("max_neighbors", "max_success", "interpret"))
def qap_sa_step_pallas_batch(C: Array, M: Array, ps: Array, fs: Array,
                             bps: Array, bfs: Array, temps: Array,
                             keys: Array, nvs: Array, *,
                             max_neighbors: int, max_success: int,
                             interpret: bool = False):
    """A whole temperature step for B chains in one launch.

    ps/bps: (B, N) current/best permutations; fs/bfs/temps: (B,) f32;
    keys: (B, 2) raw uint32 key words; nvs: (B,) int32 valid orders.
    C, M are either shared ``(N, N)`` or instance-batched ``(B0, N, N)``
    with ``B0`` dividing B (rows of one instance are contiguous -- the
    fold-into-grid contract shared with ``qap_delta_pallas_batch``).
    Returns ``(p, f, best_p, best_f)`` with the same shapes as the inputs.
    """
    n = ps.shape[-1]
    bsz = ps.shape[0]
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
    CpT = Cp.swapaxes(-2, -1)
    MpT = Mp.swapaxes(-2, -1)
    tail = jnp.broadcast_to(jnp.arange(n, n_pad, dtype=jnp.int32), (bsz, pad))
    pp = jnp.concatenate([ps.astype(jnp.int32), tail], axis=1)
    bpp = jnp.concatenate([bps.astype(jnp.int32), tail], axis=1)

    if mat_batched:
        mat_spec = pl.BlockSpec((1, n_pad, n_pad), lambda i: (i // rpt, 0, 0))
    else:
        mat_spec = pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0))
    vec_spec = pl.BlockSpec((1, n_pad), lambda i: (i, 0))
    scl_spec = pl.BlockSpec((1,), lambda i: (i,))
    p_out, f_out, bp_out, bf_out = pl.pallas_call(
        functools.partial(_sa_step_kernel, n_pad=n_pad,
                          max_neighbors=max_neighbors,
                          max_success=max_success, mat_batched=mat_batched),
        name="qap_sa_step",
        grid=(bsz,),
        in_specs=[
            vec_spec,                                      # p
            scl_spec,                                      # f
            vec_spec,                                      # best_p
            scl_spec,                                      # best_f
            scl_spec,                                      # temp
            pl.BlockSpec((1, 2), lambda i: (i, 0)),        # key words
            scl_spec,                                      # n_valid
            mat_spec,                                      # C
            mat_spec,                                      # C^T
            mat_spec,                                      # M
            mat_spec,                                      # M^T
        ],
        out_specs=(vec_spec, scl_spec, vec_spec, scl_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((bsz,), jnp.float32),
            jax.ShapeDtypeStruct((bsz, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((bsz,), jnp.float32),
        ),
        interpret=interpret,
    )(pp, fs.astype(jnp.float32), bpp, bfs.astype(jnp.float32),
      temps.astype(jnp.float32), keys.astype(jnp.uint32),
      nvs.astype(jnp.int32), Cp, CpT, Mp, MpT)
    return p_out[:, :n], f_out, bp_out[:, :n], bf_out
