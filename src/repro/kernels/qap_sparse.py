"""Pallas TPU kernels: gather-based sparse QAP objective and swap delta.

Sparse counterparts of ``qap_objective.py`` / ``qap_delta.py`` for
``core.sparse.SparseFlows`` instances (docs/DESIGN.md §10).  Neither
kernel ever holds a dense C or a whole M -- only the 8-row blocks of M and
of the padded sparse (ELL) rows that a program reads are resident, so
per-program VMEM stays O(N + D) and the FLOPs per evaluation are O(nnz)
one-hot gathers, not O(n²):

* **Objective** (``qap_objective_sparse_pallas_batch``): grid
  (permutation, flow row).  The permutation values form the
  scalar-prefetch table -- program (g, r) streams the block holding M row
  ``p[r]``, gathers ``p[cols[r, :]]`` from the resident permutation row
  and then ``M[p[r], p[cols[r, :]]]`` from the M row (two one-hot
  matmuls, ``kernels/mosaic.py``), and accumulates the row's partial sum
  ``sum_d vals[r, d] * M[p[r], p[cols[r, d]]]`` into its permutation's
  output.
* **Delta** (``qap_delta_sparse_pallas_batch``): same grid and
  scalar-prefetch table (a, b, u=p[a], v=p[b]) as the dense delta kernel,
  but the four C rows shrink from dense rows to (1, d_pad) sparse rows of
  C and C^T; the col/row sums gather ``p[cols]`` then the M rows at those
  nodes -- the same two chained one-hot gathers -- and the corner scalars
  are sparse row lookups.

Both kernels accept shared or instance-batched operands (leading ``B0``
dim on the SparseFlows leaves and M, with ``B0`` dividing the flat
permutation batch), mirroring the dense kernels' fold-into-grid
contract; correctness is validated in interpret mode against the sparse
references in ``ref.py`` and on the chip by ``chip_smoke.py``.

VMEM per program is a few 8-row blocks (double-buffered) plus the
(n_pad, 128) one-hot matrices of one 128-lane chunk of a sparse row at a
time (2 MiB each at n_pad = 4096), so wide rows cost time, not VMEM; both
kernels compile for v5e at that cap within the 16 MiB default scoped
limit (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .qap_delta import candidate_table, row_spec, rows_per_instance

Array = jax.Array

# M rows (not matrices) are what the sparse kernels keep resident, so the
# size ceiling is the row length we are willing to stream per program —
# far beyond the dense kernels' MAX_KERNEL_N full-matrix budget.
MAX_SPARSE_KERNEL_N = 4096


def _sparse_pad(S, rows: int, d_pad: int):
    """Pad the ELL blocks to (rows, d_pad): values with 0 (contributions
    vanish), column ids with 0 (a valid gather target)."""
    def widen(x, dtype):
        pads = [(0, 0)] * (x.ndim - 2) + [(0, rows - x.shape[-2]),
                                          (0, d_pad - x.shape[-1])]
        return jnp.pad(x.astype(dtype), pads)
    return (widen(S.vals, jnp.float32), widen(S.cols, jnp.int32),
            widen(S.vals_t, jnp.float32), widen(S.cols_t, jnp.int32))


def _gather_at(p: Array, cols_ref, r, rows: Array, n_pad: int) -> Array:
    """``rows[:, p[ks]]`` for the sparse row ``ks = cols[r]`` (1, d_pad):
    the permutation row ``p`` (1, n_pad) f32 read at positions ``ks``, then
    the (R, n_pad) ``rows`` read at those nodes -- two exact one-hot
    matmuls per 128 lanes of ``ks``, so a one-hot matrix never exceeds
    (n_pad, 128)."""
    width = cols_ref.shape[-1]
    out = []
    for c in range(0, width, mosaic.LANE):
        lanes = slice(None) if width == mosaic.LANE else \
            slice(c, c + mosaic.LANE)
        ks = mosaic.block_row(cols_ref, r, lanes)
        pk = mosaic.dot(p, mosaic.onehot(ks, n_pad)).astype(jnp.int32)
        out.append(mosaic.dot(rows, mosaic.onehot(pk, n_pad)))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _objective_sparse_kernel(pv_ref,          # (G*rows,) int32: p[r] per program
                             p_ref,           # (1, 1, n_pad) permutation row
                             cv_ref, cc_ref,  # 8-row ELL blocks holding row r
                             m_ref,           # 8-row block holding M row p[r]
                             out_ref,         # (1, 1, 1) f32 objective
                             *, n_pad: int, rows: int):
    g, r = pl.program_id(0), pl.program_id(1)

    @pl.when(r == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = mosaic.block_row(m_ref, pv_ref[g * rows + r])        # M[p[r], :]
    p = p_ref[0].astype(jnp.float32)
    got = _gather_at(p, cc_ref, r, m, n_pad)         # M[p[r], p[cols[r]]]
    out_ref[0] += mosaic.total(mosaic.block_row(cv_ref, r) * got)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_objective_sparse_pallas_batch(S, M: Array, ps: Array,
                                      interpret: bool = False) -> Array:
    """Sparse objectives in one launch: ps (B, P, N) -> (B, P) f32.

    ``S`` leaves are (N, D) shared blocks or (B, N, D) instance-batched
    (M correspondingly (N, N) or (B, N, N)) — the batched solvers' case,
    where the dispatch layer folds the instance axis into the grid.  One
    grid step per (permutation, flow row); each row's partial sum
    accumulates into its permutation's output (f32 — exact on integer
    instances).
    """
    bsz, p_cnt, n = ps.shape
    mat_batched = M.ndim == 3
    if mat_batched and M.shape[0] != bsz:
        raise ValueError(
            f"batched S/M leading dim {M.shape[0]} must equal B={bsz}")
    n_pad = mosaic.padded_order(n)
    rows = mosaic.pad_to(n, mosaic.SUBLANE)
    d_pad = mosaic.pad_to(max(S.cols.shape[-1], mosaic.LANE), mosaic.LANE)

    cv, cc, _, _ = _sparse_pad(S, rows, d_pad)
    Mp = mosaic.pad_matrix(M, n_pad, n_pad)
    pp = mosaic.pad_perms(ps.reshape(bsz * p_cnt, n), n_pad)  # (G, n_pad)
    pv = pp[:, :rows].reshape(-1)                     # p[r] per program
    pp3 = pp[:, None, :]
    sub = mosaic.SUBLANE

    outs = []
    for start, cnt in mosaic.chunks(bsz * p_cnt, rows):
        if mat_batched:
            inst = lambda g, s=start: (s + g) // p_cnt
            ell = pl.BlockSpec((1, sub, d_pad),
                               lambda g, r, t: (inst(g), r // sub, 0))
            mrow = pl.BlockSpec(
                (1, sub, n_pad),
                lambda g, r, t: (inst(g), t[g * rows + r] // sub, 0))
        else:
            ell = pl.BlockSpec((sub, d_pad), lambda g, r, t: (r // sub, 0))
            mrow = pl.BlockSpec(
                (sub, n_pad), lambda g, r, t: (t[g * rows + r] // sub, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cnt, rows),
            in_specs=[
                pl.BlockSpec((1, 1, n_pad),
                             lambda g, r, t, s=start: (s + g, 0, 0)),
                ell,                                      # vals row r
                ell,                                      # cols row r
                mrow,                                     # M[p[r], :]
            ],
            out_specs=pl.BlockSpec((1, 1, 1), lambda g, r, t: (g, 0, 0)),
        )
        outs.append(pl.pallas_call(
            functools.partial(_objective_sparse_kernel, n_pad=n_pad,
                              rows=rows),
            name="qap_objective_sparse",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cnt, 1, 1), jnp.float32),
            interpret=interpret,
        )(pv[start * rows:(start + cnt) * rows], pp3, cv, cc, Mp))
    return jnp.concatenate(outs).reshape(bsz, p_cnt)


def _delta_sparse_kernel(info_ref,            # (4*T,) int32: a, b, u, v
                         p_ref,               # (1, 1, n_pad) permutation row
                         cv_a, cv_b,          # ELL blocks, C rows a, b: values
                         cc_a, cc_b,          # ... C rows a, b: cols
                         tv_a, tv_b,          # ... C^T rows a, b: values
                         tc_a, tc_b,          # ... C^T rows a, b: cols
                         m_row_u, m_row_v,    # 8-row blocks of M
                         mt_row_u, mt_row_v,  # 8-row blocks of M^T
                         out_ref,             # (1, 1, 1) f32
                         *, n_pad: int):
    q = 4 * pl.program_id(0)
    a, b = info_ref[q], info_ref[q + 1]
    u, v = info_ref[q + 2], info_ref[q + 3]

    row = mosaic.block_row
    mu, mv = row(m_row_u, u), row(m_row_v, v)          # M[u, :], M[v, :]
    mtu, mtv = row(mt_row_u, u), row(mt_row_v, v)      # M[:, u], M[:, v]
    rows = mosaic.stack_rows(mtv, mtu, mv, mu)
    p = p_ref[0].astype(jnp.float32)

    def part(cc, cv, r, hi):
        """One sparse row r: sum_d w * (rows[hi][p[k]] - rows[hi+1][p[k]])
        over its stored entries k outside {a, b}."""
        ks, ws = row(cc, r), row(cv, r)
        g = _gather_at(p, cc, r, rows, n_pad)
        return mosaic.total(jnp.where((ks != a) & (ks != b),
                                      ws * (g[hi:hi + 1] - g[hi + 1:hi + 2]),
                                      0.0))

    # Column terms read rows a/b of C^T: M[p[k], v] - M[p[k], u]; row
    # terms rows a/b of C: M[v, p[l]] - M[u, p[l]].
    col = part(tc_a, tv_a, a, 0) - part(tc_b, tv_b, b, 0)
    rowt = part(cc_a, cv_a, a, 2) - part(cc_b, cv_b, b, 2)

    # Corner scalars: C entries via sparse row lookups, M entries via
    # picks from the already-resident rows.
    def centry(cc, cv, r, j):                # C[r, j] via the sparse row r
        return mosaic.total(jnp.where(row(cc, r) == j, row(cv, r), 0.0))

    caa, cbb = centry(cc_a, cv_a, a, a), centry(cc_b, cv_b, b, b)
    cab, cba = centry(cc_a, cv_a, a, b), centry(cc_b, cv_b, b, a)
    pick = mosaic.pick
    muu, mvv = pick(mu, u), pick(mv, v)
    muv, mvu = pick(mu, v), pick(mv, u)        # M[u, v], M[v, u]

    corner = ((caa - cbb) * (mvv - muu)
              + cab * (mvu - muv)
              + cba * (muv - mvu))
    out_ref[0] = col + rowt + corner


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_delta_sparse_pallas_batch(S, M: Array, ps: Array, pairs: Array,
                                  interpret: bool = False) -> Array:
    """Sparse leading-batch swap deltas in one launch.

    ps: (B, N); pairs: (B, K, 2)  ->  (B, K) f32; grid B*K, candidate q
    works on permutation row q // K.  ``S`` leaves/M are shared or
    instance-batched with ``B0`` dividing B (rows r*B//B0 .. belong to
    instance r), exactly like the dense ``qap_delta_pallas_batch``.
    """
    n = ps.shape[-1]
    bsz, k = pairs.shape[0], pairs.shape[1]
    mat_batched = M.ndim == 3
    rpt = rows_per_instance(M, bsz)
    n_pad = mosaic.padded_order(n)
    rows = mosaic.pad_to(n, mosaic.SUBLANE)
    d_pad = mosaic.pad_to(max(S.cols.shape[-1], mosaic.LANE), mosaic.LANE)

    cv, cc, tv, tc = _sparse_pad(S, rows, d_pad)
    Mp = mosaic.pad_matrix(M, n_pad, n_pad)
    MpT = Mp.swapaxes(-2, -1)
    pp = mosaic.pad_perms(ps, n_pad)
    info = candidate_table(pp, pairs)
    pp3 = pp[:, None, :]

    outs = []
    for start, cnt in mosaic.chunks(bsz * k, 4):
        ell = functools.partial(row_spec, cv.shape, mat_batched, start,
                                k * rpt)
        mrow = functools.partial(row_spec, Mp.shape, mat_batched, start,
                                 k * rpt)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cnt,),
            in_specs=[
                pl.BlockSpec((1, 1, n_pad),
                             lambda i, t, s=start: ((s + i) // k, 0, 0)),
                ell(0), ell(1),                 # C rows a, b: values
                ell(0), ell(1),                 # C rows a, b: cols
                ell(0), ell(1),                 # C^T rows a, b: values
                ell(0), ell(1),                 # C^T rows a, b: cols
                mrow(2), mrow(3),               # M[u, :], M[v, :]
                mrow(2), mrow(3),               # M^T[u, :], M^T[v, :]
            ],
            out_specs=pl.BlockSpec((1, 1, 1), lambda i, t: (i, 0, 0)),
        )
        outs.append(pl.pallas_call(
            functools.partial(_delta_sparse_kernel, n_pad=n_pad),
            name="qap_delta_sparse",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cnt, 1, 1), jnp.float32),
            interpret=interpret,
        )(info[4 * start:4 * (start + cnt)], pp3,
          cv, cv, cc, cc, tv, tv, tc, tc, Mp, Mp, MpT, MpT))
    return jnp.concatenate(outs).reshape(bsz, k)
