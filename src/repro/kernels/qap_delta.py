"""Pallas TPU kernels: batched O(N) swap-delta evaluation, in two forms.

The SA hot loop: the paper (S5) credits simulated annealing's speed to
incremental objective recomputation -- a swap of two positions changes F by a
quantity computable in O(N).  Both forms score B permutations x K candidate
swaps each in one kernel launch; the wide launch is what the
acceptance-event SA loop and the 2-swap polish dispatch: all of a
temperature level's remaining candidates are scored against the current
state in one launch instead of a depth-K sequential scan (docs/DESIGN.md §4).
``ops.delta_form`` picks the form from the padded order alone.

**Row form** (``qap_delta_rows_pallas_batch``, n_pad <= ``ROW_FORM_MAX_N``):
one grid step per permutation row scores all of that row's candidates.  C,
C^T, M and M^T arrive as whole ``(n_pad, n_pad)`` blocks indexed by the
row's instance, so consecutive rows of one instance reuse the resident
blocks and each matrix is read from HBM once a launch.  The row's
candidates arrive as one int32 block (rows a, b, u = p[a], v = p[b], lanes
= candidates).  Every gather is an exact one-hot matmul
(``kernels/mosaic.py``): the permutation one-hot forms M[p, :] and
M[:, p]^T once a step, and one (n_pad x n_pad) . (n_pad x K) product per
vector then gives C[:, a], C[a, :], M[p, v], M[v, p] and the rest for every
candidate at once.  The MXU sets this form's pace, so its dots split the
float32 operand into three exact bfloat16 parts (``mosaic.dot_onehot``):
the bits of ``HIGHEST`` at about half the passes.  The output is one
lane-dense row per step.

**Candidate form** (``qap_delta_pallas_batch``, orders above the row
form's cap, up to ``MAX_KERNEL_N``): one grid step per candidate.  Its eight
matrix rows (C[a,:], C[b,:], C[:,a], C[:,b] via C^T, and M rows/cols for
the swapped nodes u, v) arrive as the 8-row blocks that hold them, picked
by BlockSpec index maps from a scalar-prefetch table and selected inside
the kernel -- no full-matrix residency, so the streamed working set is O(N)
per candidate.  The four M vectors are gathered by the permutation in one
one-hot MXU matmul (an (8, n_pad) x (n_pad, n_pad) product).

Why two forms: at the engine's bucket orders the candidate form pays
Pallas's fixed cost of a grid step for every candidate (B x K steps, 1600
at the SA loop's shapes) and streams each matrix row about 50 times a
launch; the row form takes B steps and reads each matrix once.  But it
forms M[p, :] and M[:, p]^T, 2 n_pad^3 multiply-adds, for every row, so
its cost grows as n_pad^3 where the candidate form's grows as K n_pad^2.
Measured on a v5e at the SA loop's 25 candidates, the row form is the
faster up to n_pad = 512 and the slower at 640 (docs/DESIGN.md §4), so
``ROW_FORM_MAX_N`` is 512; the compiler would fit it in the default scoped
VMEM up to 640.  Candidates beyond ``ROW_LANES`` are taken in blocks of
that many lanes, which bounds its (n_pad x K) temporaries.  On integer
instances every partial sum is an exact integer in float32, so the two
forms and ``ref.qap_delta_ref`` agree bit for bit; interpret-mode tests
check that, and ``chip_smoke.py`` does on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .qap_objective import MAX_KERNEL_N, matrix_spec

Array = jax.Array

ROW_FORM_MAX_N = 512   # largest padded order of the row form (speed)
ROW_LANES = 256        # most candidates one row-form grid step scores


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


def _swap_nodes(pp: Array, pairs: Array):
    """(a, b, p[a], p[b]), each (B, K) int32, for padded permutations
    ``pp`` (B, n_pad) and candidate swaps ``pairs`` (B, K, 2)."""
    ab = pairs.astype(jnp.int32)
    a, b = ab[..., 0], ab[..., 1]
    return (a, b, jnp.take_along_axis(pp, a, axis=1),
            jnp.take_along_axis(pp, b, axis=1))


def candidate_table(pp: Array, pairs: Array) -> Array:
    """Flat (4 * B * K,) scalar-prefetch table of (a, b, p[a], p[b]) per
    candidate."""
    return jnp.stack(_swap_nodes(pp, pairs), axis=-1).reshape(-1)


def rows_per_instance(mats: Array, bsz: int) -> int:
    """Permutation rows per instance: ``B // B0`` for instance-batched
    ``(B0, N, N)`` matrices (``B0`` must divide ``B``), 1 for shared."""
    if mats.ndim != 3:
        return 1
    if bsz % mats.shape[0] != 0:
        raise ValueError(
            f"batched C/M leading dim {mats.shape[0]} must divide B={bsz}")
    return bsz // mats.shape[0]


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
    rpt = rows_per_instance(C, bsz)
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


def _row_kernel(cand_ref,                # (1, 8, L) int32: rows a, b, u, v
                p_ref,                   # (1, 1, n_pad) this row's permutation
                c_ref, ct_ref,           # C, C^T (leading unit dim if batched)
                m_ref, mt_ref,           # M, M^T
                out_ref,                 # (1, 1, L) f32
                *, n_pad: int):
    mat = lambda ref: ref[0] if len(ref.shape) == 3 else ref[...]
    C, CT, M, MT = map(mat, (c_ref, ct_ref, m_ref, mt_ref))
    cand = cand_ref[0]
    a, b, u, v = (cand[i:i + 1] for i in range(4))       # (1, L) each

    # P[i, m] = [p[i] == m]: the permutation's rows of M and of M^T.
    P = mosaic.onehot(p_ref[0], n_pad).T
    mp = mosaic.onehot_dot(P, M)                         # M[p_i, m]
    mtp = mosaic.onehot_dot(P, MT)                       # M[m, p_i]
    # One-hot columns oa[j, k] = [a_k == j]: x @ oa gathers x[:, a_k].
    oa, ob, ou, ov = (mosaic.onehot(x, n_pad) for x in (a, b, u, v))
    dot = mosaic.dot_onehot
    cta, ctb = dot(C, oa), dot(C, ob)                    # C[i, a], C[i, b]
    ca, cb = dot(CT, oa), dot(CT, ob)                    # C[a, i], C[b, i]
    g0, g1 = dot(mp, ov), dot(mp, ou)                    # M[p_i, v], M[p_i, u]
    g2, g3 = dot(mtp, ov), dot(mtp, ou)                  # M[v, p_i], M[u, p_i]

    i = jax.lax.broadcasted_iota(jnp.int32, oa.shape, 0)
    mask = (i != a) & (i != b)
    total = lambda x: jnp.sum(x, axis=0, keepdims=True)
    col = total(jnp.where(mask, (cta - ctb) * (g0 - g1), 0.0))
    row = total(jnp.where(mask, (ca - cb) * (g2 - g3), 0.0))

    # x[a_k, k] is the sum of oa * x down the column; p[a] = u, p[b] = v.
    caa, cbb = total(oa * ca), total(ob * cb)            # C[a, a], C[b, b]
    cab, cba = total(ob * ca), total(oa * cb)            # C[a, b], C[b, a]
    muu, mvv = total(oa * g3), total(ob * g2)            # M[u, u], M[v, v]
    muv, mvu = total(ob * g3), total(oa * g2)            # M[u, v], M[v, u]
    corner = ((caa - cbb) * (mvv - muu)
              + cab * (mvu - muv)
              + cba * (muv - mvu))
    out_ref[0] = col + row + corner


def _row_candidates(pp: Array, pairs: Array, k_pad: int) -> Array:
    """(B, 8, k_pad) int32 block of each permutation row's candidates:
    rows a, b, p[a], p[b] (the rest zero), lanes past K zero."""
    cand = jnp.stack(_swap_nodes(pp, pairs), axis=1)            # (B, 4, K)
    return jnp.pad(cand, ((0, 0), (0, mosaic.SUBLANE - 4),
                          (0, k_pad - cand.shape[-1])))


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_delta_rows_pallas_batch(C: Array, M: Array, ps: Array, pairs: Array,
                                interpret: bool = False) -> Array:
    """Row form of :func:`qap_delta_pallas_batch`: the same contract (ps
    (B, N), pairs (B, K, 2), shared or instance-batched C/M -> (B, K)
    f32), with a grid of one step per permutation row and block of at
    most ``ROW_LANES`` candidates."""
    n = ps.shape[-1]
    bsz, k = pairs.shape[0], pairs.shape[1]
    mat_batched = C.ndim == 3
    rpt = rows_per_instance(C, bsz)
    n_pad = mosaic.padded_order(n)
    if n_pad > ROW_FORM_MAX_N:
        raise ValueError(
            f"padded N={n_pad} exceeds the row form's cap {ROW_FORM_MAX_N}")
    lanes = min(mosaic.pad_to(k, mosaic.LANE), ROW_LANES)
    k_pad = mosaic.pad_to(k, lanes)

    Cp = mosaic.pad_matrix(C, n_pad, n_pad)
    Mp = mosaic.pad_matrix(M, n_pad, n_pad)
    pp = mosaic.pad_perms(ps, n_pad)                             # (B, n_pad)
    mat_spec = matrix_spec(n_pad, mat_batched, lambda r, j: r // rpt)
    out = pl.pallas_call(
        functools.partial(_row_kernel, n_pad=n_pad),
        name="qap_delta",
        grid=(bsz, k_pad // lanes),
        in_specs=[
            pl.BlockSpec((1, mosaic.SUBLANE, lanes), lambda r, j: (r, 0, j)),
            pl.BlockSpec((1, 1, n_pad), lambda r, j: (r, 0, 0)),
            mat_spec, mat_spec, mat_spec, mat_spec,      # C, C^T, M, M^T
        ],
        out_specs=pl.BlockSpec((1, 1, lanes), lambda r, j: (r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1, k_pad), jnp.float32),
        interpret=interpret,
    )(_row_candidates(pp, pairs, k_pad), pp[:, None, :],
      Cp, Cp.swapaxes(-2, -1), Mp, Mp.swapaxes(-2, -1))
    return out[:, 0, :k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_delta_pallas(C: Array, M: Array, p: Array, pairs: Array,
                     interpret: bool = False) -> Array:
    """Batched swap deltas.  C, M: (N, N); p: (N,); pairs: (K, 2) -> (K,) f32."""
    return qap_delta_pallas_batch(C, M, p[None], pairs[None],
                                  interpret=interpret)[0]
