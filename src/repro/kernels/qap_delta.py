"""Pallas TPU kernel: batched O(N) swap-delta evaluation.

The SA hot loop: the paper (S5) credits simulated annealing's speed to
incremental objective recomputation -- a swap of two positions changes F by a
quantity computable in O(N).  ``qap_delta_pallas_batch`` evaluates B
permutations x K candidate swaps each in one kernel launch (grid B*K, one
program instance per candidate); ``qap_delta_pallas`` is the single-
permutation special case.  The wide form is what the acceptance-event SA
loop dispatches: all of a temperature level's remaining candidates are
scored against the current state in one launch instead of a depth-K
sequential scan (docs/DESIGN.md §4).

TPU adaptation: the candidate's eight matrix rows (C[a,:], C[b,:], C[:,a],
C[:,b] via C^T, and M rows/cols for the swapped nodes u = p[a], v = p[b])
arrive as the 8-row blocks that hold them, picked by BlockSpec index maps
from a scalar-prefetch table and selected inside the kernel
(``kernels/mosaic.py``) -- no full-matrix residency, so the streamed
working set is O(N) per candidate; consecutive candidates of the same
permutation reuse the resident permutation block.  The four M vectors are
gathered by the permutation in one one-hot MXU matmul (an (8, n_pad) x
(n_pad, n_pad) product), which bounds the order at ``MAX_KERNEL_N`` like
the objective kernel.  Correctness is checked in interpret mode against
``ref.qap_delta_ref`` and on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .qap_objective import MAX_KERNEL_N

Array = jax.Array


def _delta_kernel(info_ref,            # (4*T,) int32 scalar prefetch: a, b, u, v
                  p_ref,               # (1, 1, n_pad) this candidate's permutation
                  c_row_a, c_row_b,    # 8-row blocks of C holding rows a, b
                  ct_row_a, ct_row_b,  # ... of C^T (columns of C)
                  m_row_u, m_row_v,    # ... of M holding rows u, v
                  mt_row_u, mt_row_v,  # ... of M^T (columns of M)
                  out_ref,             # (1, 1, 1) f32
                  *, n_pad: int):
    q = 4 * pl.program_id(0)
    a, b = info_ref[q], info_ref[q + 1]
    u, v = info_ref[q + 2], info_ref[q + 3]

    row = mosaic.block_row
    ca, cb = row(c_row_a, a), row(c_row_b, b)          # C[a, :], C[b, :]
    cta, ctb = row(ct_row_a, a), row(ct_row_b, b)      # C[:, a], C[:, b]
    mu, mv = row(m_row_u, u), row(m_row_v, v)          # M[u, :], M[v, :]
    mtu, mtv = row(mt_row_u, u), row(mt_row_v, v)      # M[:, u], M[:, v]

    # The node-indexed rows gathered by the current permutation, in one
    # one-hot matmul: g[0] = M[p, v], g[1] = M[p, u], g[2] = M[v, p],
    # g[3] = M[u, p].
    g = mosaic.dot(mosaic.stack_rows(mtv, mtu, mv, mu),
                   mosaic.onehot(p_ref[0], n_pad))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    mask = (lane != a) & (lane != b)
    col = mosaic.total(jnp.where(mask, (cta - ctb) * (g[0:1] - g[1:2]), 0.0))
    row_ = mosaic.total(jnp.where(mask, (ca - cb) * (g[2:3] - g[3:4]), 0.0))

    pick = mosaic.pick
    caa, cbb = pick(cta, a), pick(ctb, b)              # C[a, a], C[b, b]
    cab, cba = pick(ca, b), pick(cb, a)                # C[a, b], C[b, a]
    muu, mvv = pick(mu, u), pick(mv, v)                # M[u, u], M[v, v]
    muv, mvu = pick(mu, v), pick(mv, u)                # M[u, v], M[v, u]
    corner = ((caa - cbb) * (mvv - muu)
              + cab * (mvu - muv)
              + cba * (muv - mvu))
    out_ref[0] = col + row_ + corner


def candidate_table(pp: Array, pairs: Array) -> Array:
    """Flat (4 * B * K,) scalar-prefetch table of (a, b, p[a], p[b]) per
    candidate, for padded permutations ``pp`` (B, n_pad) and ``pairs``
    (B, K, 2)."""
    ab = pairs.astype(jnp.int32)
    u = jnp.take_along_axis(pp, ab[..., 0], axis=1)
    v = jnp.take_along_axis(pp, ab[..., 1], axis=1)
    return jnp.stack([ab[..., 0], ab[..., 1], u, v], axis=-1).reshape(-1)


def row_spec(shape, batched: bool, start: int, per_instance: int, col: int):
    """8-row block of a (…, rows, width) array holding the row named by
    table column ``col`` of chunk-local candidate ``i`` (candidate
    ``start + i`` overall; ``per_instance`` candidates per instance)."""
    width = shape[-1]
    if batched:
        return pl.BlockSpec(
            (1, mosaic.SUBLANE, width),
            lambda i, t: ((start + i) // per_instance,
                          t[4 * i + col] // mosaic.SUBLANE, 0))
    return pl.BlockSpec((mosaic.SUBLANE, width),
                        lambda i, t: (t[4 * i + col] // mosaic.SUBLANE, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_delta_pallas_batch(C: Array, M: Array, ps: Array, pairs: Array,
                           interpret: bool = False) -> Array:
    """Leading-batch swap deltas in one launch.

    ps: (B, N) one permutation per batch row; pairs: (B, K, 2) candidate
    swaps per row  ->  (B, K) f32.  One kernel launch with grid B*K
    (split into a few launches when the candidate table outgrows SMEM);
    candidate q works on permutation row q // K.  C, M are either shared
    ``(N, N)`` matrices or instance-batched ``(B0, N, N)`` with ``B0``
    dividing B (rows ``r*B//B0 .. (r+1)*B//B0 - 1`` belong to instance r
    -- the batched solvers' case, where the dispatch layer folds the
    instance axis into the leading batch instead of vmapping the kernel).
    """
    n = ps.shape[-1]
    bsz, k = pairs.shape[0], pairs.shape[1]
    mat_batched = C.ndim == 3
    if mat_batched and (bsz % C.shape[0] != 0):
        raise ValueError(
            f"batched C/M leading dim {C.shape[0]} must divide B={bsz}")
    rpt = (bsz // C.shape[0]) if mat_batched else 1  # perm rows per instance
    n_pad = mosaic.padded_order(n)
    if n_pad > MAX_KERNEL_N:
        raise ValueError(f"padded N={n_pad} exceeds kernel cap {MAX_KERNEL_N}")

    Cp = mosaic.pad_matrix(C, n_pad, n_pad)
    Mp = mosaic.pad_matrix(M, n_pad, n_pad)
    CpT = Cp.swapaxes(-2, -1)
    MpT = Mp.swapaxes(-2, -1)
    pp = mosaic.pad_perms(ps, n_pad)                             # (B, n_pad)
    info = candidate_table(pp, pairs)
    pp3 = pp[:, None, :]

    outs = []
    for start, cnt in mosaic.chunks(bsz * k, 4):
        spec = functools.partial(row_spec, Cp.shape, mat_batched, start,
                                 k * rpt)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cnt,),
            in_specs=[
                pl.BlockSpec((1, 1, n_pad),
                             lambda i, t, s=start: ((s + i) // k, 0, 0)),
                spec(0), spec(1),                       # C[a, :], C[b, :]
                spec(0), spec(1),                       # C^T[a, :], C^T[b, :]
                spec(2), spec(3),                       # M[u, :], M[v, :]
                spec(2), spec(3),                       # M^T[u, :], M^T[v, :]
            ],
            out_specs=pl.BlockSpec((1, 1, 1), lambda i, t: (i, 0, 0)),
        )
        outs.append(pl.pallas_call(
            functools.partial(_delta_kernel, n_pad=n_pad),
            name="qap_delta",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cnt, 1, 1), jnp.float32),
            interpret=interpret,
        )(info[4 * start:4 * (start + cnt)], pp3,
          Cp, Cp, CpT, CpT, Mp, Mp, MpT, MpT))
    return jnp.concatenate(outs).reshape(bsz, k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_delta_pallas(C: Array, M: Array, p: Array, pairs: Array,
                     interpret: bool = False) -> Array:
    """Batched swap deltas.  C, M: (N, N); p: (N,); pairs: (K, 2) -> (K,) f32."""
    return qap_delta_pallas_batch(C, M, p[None], pairs[None],
                                  interpret=interpret)[0]
