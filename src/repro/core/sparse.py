"""Padded CSR-ish (ELL) storage for sparse flow matrices.

Real program graphs are sparse — VieM (Schulz & Träff, arXiv:1703.05509)
frames process mapping as *sparse* quadratic assignment — while every
dense path in this repo materializes C as (N, N), making objective and
delta evaluation O(n²) regardless of how many flows are actually nonzero.
:class:`SparseFlows` breaks that wall (docs/DESIGN.md §10):

* **Padded row blocks, static shapes.**  Row k keeps its nonzero column
  ids in ``cols[k, :]`` (ascending) and their values in ``vals[k, :]``,
  both padded to a shared width ``D`` >= the max row degree.  Padding entries
  carry value 0 (their column id is an arbitrary in-range index), so
  every consumer can process full (N, D) blocks without ragged logic —
  the shape is static, which keeps the structure jit-traceable,
  batchable (a leading instance axis maps over every leaf), and
  streamable by Pallas BlockSpecs.
* **Both orientations.**  ``cols_t``/``vals_t`` hold the same layout for
  C^T, so delta evaluation can read column ``a`` of an asymmetric C as a
  contiguous row — the sparse analogue of the dense kernels' C^T input.
* **A pytree.**  ``SparseFlows`` is a NamedTuple of arrays: it passes
  through ``jax.jit`` / ``vmap`` / ``lax`` control flow unchanged, and
  the solver entry points accept it wherever they accept a dense ``C``
  (the ``.shape`` property mimics the dense (N, N) view the solvers
  consult for sizes).

Conversion (:func:`from_dense`) is host-side numpy — which entries are
nonzero is data, so it cannot run under jit; convert once per instance,
then everything downstream is traced.  :func:`to_dense` is traceable and
exact: scattering the padded blocks back adds only zeros on padding.

The multilevel pipeline's host side works on :class:`Nonzeros`, a flow
matrix's stored entries in row-major order, built from the dense matrix
with one pass (:func:`nonzeros`).  :func:`from_nonzeros` builds the ELL
blocks from them in O(nnz), and :func:`ell_width` gives a width fixed
by the order and the density class, so that instances of one order
share their programs.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_LANE = 128     # the sparse kernels take ELL rows in 128-lane blocks


class SparseFlows(NamedTuple):
    """ELL-format flow matrix (see module docstring).

    Leaves may carry leading batch dims: ``cols``/``vals``/``cols_t``/
    ``vals_t`` are (..., N, D), ``deg``/``deg_t`` are (..., N).  Padding
    entries have value 0; their column ids are valid in-range indices, so
    gathers through them are safe and their contributions vanish.
    ``deg`` records the *stored pattern's* row degrees (masking zeroes
    values but keeps the pattern).
    """
    cols: Array     # (..., N, D) int32 column ids of C's rows
    vals: Array     # (..., N, D) f32 values of C's rows
    cols_t: Array   # (..., N, D) int32 column ids of C^T's rows
    vals_t: Array   # (..., N, D) f32 values of C^T's rows
    deg: Array      # (..., N) int32 nonzeros per row of C
    deg_t: Array    # (..., N) int32 nonzeros per row of C^T

    @property
    def n(self) -> int:
        return self.cols.shape[-2]

    @property
    def max_degree(self) -> int:
        return self.cols.shape[-1]

    @property
    def shape(self) -> Tuple[int, ...]:
        """The dense-equivalent shape (..., N, N) — call sites that only
        need sizes (``C.shape[0]``) work unchanged on sparse flows."""
        return self.cols.shape[:-1] + (self.n,)

    @property
    def dtype(self):
        return self.vals.dtype

    def nnz(self) -> Array:
        """Stored nonzeros (per leading batch entry, if any)."""
        return self.deg.sum(axis=-1)


def max_degree(C) -> int:
    """Padded width needed to store ``C``: max nonzeros over rows of C
    and of C^T (host-side; accepts leading batch dims)."""
    A = np.asarray(C)
    nz = A != 0
    d = max(int(nz.sum(axis=-1).max(initial=0)),
            int(nz.sum(axis=-2).max(initial=0)))
    return max(d, 1)


@dataclass(frozen=True, eq=False)
class Nonzeros:
    """A flow matrix's stored entries, host-side: ``rows``/``cols``/
    ``vals`` in row-major order (columns ascending inside a row), no
    explicit zeros.  Every host step of the multilevel pipeline reads
    these in O(nnz) instead of sweeping the (n, n) matrix."""
    n: int
    rows: np.ndarray    # (nnz,) int32, ascending
    cols: np.ndarray    # (nnz,) int32, ascending inside each row
    vals: np.ndarray    # (nnz,) float32 (or coalesce's dtype), nonzero

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])

    @functools.cached_property
    def deg(self) -> np.ndarray:
        """Stored entries per row, (n,) int64."""
        return np.bincount(self.rows, minlength=self.n)

    @functools.cached_property
    def deg_t(self) -> np.ndarray:
        """Stored entries per column, (n,) int64."""
        return np.bincount(self.cols, minlength=self.n)

    @property
    def max_degree(self) -> int:
        """Stored entries of the densest row or column."""
        return max(int(self.deg.max(initial=0)),
                   int(self.deg_t.max(initial=0)))

    @functools.cached_property
    def T(self) -> "Nonzeros":
        """C^T's entries, row-major: one stable sort of C's by column."""
        t = np.argsort(self.cols.astype(_index_dtype(self.n)), kind="stable")
        return Nonzeros(n=self.n, rows=self.cols.take(t),
                        cols=self.rows.take(t), vals=self.vals.take(t))

    def dense(self, order: Optional[int] = None) -> np.ndarray:
        """The (order, order) float32 matrix (``order`` defaults to n),
        zero past n."""
        A = np.zeros((order or self.n,) * 2, np.float32)
        A[self.rows, self.cols] = self.vals
        return A


def _index_dtype(n: int):
    """The narrowest signed integer type that holds indices below ``n``:
    numpy sorts 16-bit keys stably by radix, in O(len)."""
    return np.int16 if n <= 1 << 15 else np.int64


def coalesce(n: int, rows, cols, vals, dtype=np.float32) -> Nonzeros:
    """The order-``n`` matrix whose entries are the sums of ``vals`` at
    (``rows``, ``cols``): duplicates summed in float64 in their given
    order, then rounded to ``dtype``, and sums of zero dropped — what a
    dense float64 accumulation of the same entries, rounded to ``dtype``,
    holds.  One sort of (row, column, position) packed in an int64, so
    equal entries keep their given order."""
    key = np.asarray(rows, np.int64) * n + np.asarray(cols)
    shift = max(key.shape[0] - 1, 1).bit_length()
    if max(n * n - 1, 1).bit_length() + shift > 63:
        raise ValueError(f"{key.shape[0]} entries of order {n} overflow "
                         "the sort key")
    packed = np.sort((key << shift) | np.arange(key.shape[0]))
    o, key = packed & ((1 << shift) - 1), packed >> shift
    head = np.ones(key.shape[0], bool)
    head[1:] = key[1:] != key[:-1]
    starts = np.flatnonzero(head)
    sums = (np.add.reduceat(np.asarray(vals, np.float64).take(o), starts)
            if starts.size else np.zeros(0)).astype(dtype)
    keep = sums != 0
    rows, cols = np.divmod(key.take(starts[keep]), n)
    return Nonzeros(n=n, rows=rows.astype(np.int32),
                    cols=cols.astype(np.int32), vals=sums[keep])


def nonzeros(C) -> Nonzeros:
    """The stored entries of a dense (n, n) flow matrix, one pass."""
    A = np.asarray(C, dtype=np.float32)
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise ValueError(f"flow matrix must be (N, N), got {A.shape}")
    n = A.shape[0]
    flat = np.flatnonzero(A != 0)
    rows, cols = np.divmod(flat, n)
    return Nonzeros(n=n, rows=rows.astype(np.int32),
                    cols=cols.astype(np.int32), vals=A.ravel()[flat])


def ell_width(flows: Nonzeros, order: int) -> int:
    """The padded width for ``flows`` held at ``order`` (>= its order):
    fixed by the order and the flows' density class, never by their
    densest row, so every instance of one recipe at one order shares a
    program.

    The class is the density (nonzeros over n²) rounded up to a power of
    two; the width is 1.5 times the class's mean row at ``order``, in
    whole 128-lane blocks, capped at ``order``.  Flows denser than their
    class allows (a row above that width) get the next power of two that
    holds their densest row: still a short ladder, never this instance's
    own maximum.  docs/DESIGN.md §10 gives the margins.
    """
    n, nnz = flows.n, flows.nnz
    if nnz == 0:
        return min(order, _LANE)
    density_class = 2.0 ** math.ceil(math.log2(nnz / (n * n)))
    width = min(order, -(-math.ceil(1.5 * density_class * order) // _LANE)
                * _LANE)
    d = flows.max_degree
    if d > width:
        width = min(order, 1 << (d - 1).bit_length())
    return width


def _entries_to_ell(flows: Nonzeros, order: int, width: int):
    """One orientation's padded blocks at ``order`` rows: each row's
    entries first, in order; padding slots hold value 0 and column 0."""
    start = np.zeros(flows.n + 1, np.int64)
    np.cumsum(flows.deg, out=start[1:])
    rows = flows.rows.astype(np.int64)
    slot = rows * width + (np.arange(flows.nnz) - start.take(rows))
    cols = np.zeros(order * width, np.int32)
    vals = np.zeros(order * width, np.float32)
    cols[slot] = flows.cols
    vals[slot] = flows.vals
    deg = np.zeros(order, np.int32)
    deg[:flows.n] = flows.deg
    return cols.reshape(order, width), vals.reshape(order, width), deg


def from_nonzeros(flows: Nonzeros, order: int, width: int) -> SparseFlows:
    """:class:`SparseFlows` of ``flows`` padded to ``order`` rows and
    ``width`` slots, in O(nnz + order × width): the same stored entries,
    in the same order, as ``from_dense`` of the padded dense matrix.
    ``width`` must hold the densest row and column."""
    if width < flows.max_degree:
        raise ValueError(f"width={width} < max row degree {flows.max_degree}")
    cols, vals, deg = _entries_to_ell(flows, order, width)
    cols_t, vals_t, deg_t = _entries_to_ell(flows.T, order, width)
    return SparseFlows(cols=jnp.asarray(cols), vals=jnp.asarray(vals),
                       cols_t=jnp.asarray(cols_t), vals_t=jnp.asarray(vals_t),
                       deg=jnp.asarray(deg), deg_t=jnp.asarray(deg_t))


def _rows_to_ell(A: np.ndarray, width: int):
    """One orientation's padded blocks: nonzero columns first (ascending),
    values gathered in place — entries past each row's degree gather a
    zero of A, so padding values are exactly 0."""
    n = A.shape[0]
    order = np.argsort(A == 0, axis=1, kind="stable")   # False < True
    cols = order[:, :width].astype(np.int32)
    vals = np.take_along_axis(A, cols, axis=1).astype(np.float32)
    deg = (A != 0).sum(axis=1).astype(np.int32)
    return cols, vals, deg


def from_dense(C, width: Optional[int] = None) -> SparseFlows:
    """Convert a dense (..., N, N) flow matrix to :class:`SparseFlows`.

    Host-side (numpy): the padded width is data-dependent.  ``width``
    pins the padded block width (e.g. to share one jit program across
    instances of different density); it must hold the densest row.
    """
    A = np.asarray(C, dtype=np.float32)
    if A.ndim < 2 or A.shape[-1] != A.shape[-2]:
        raise ValueError(f"flow matrix must be (..., N, N), got {A.shape}")
    d = max_degree(A)
    if width is None:
        width = d
    elif width < d:
        raise ValueError(f"width={width} < max row degree {d}")
    if A.ndim > 2:
        lead = A.shape[:-2]
        parts = [from_dense(a, width) for a in A.reshape((-1,) + A.shape[-2:])]
        return SparseFlows(*(
            jnp.stack(leaf).reshape(lead + leaf[0].shape)
            for leaf in zip(*parts)))
    cols, vals, deg = _rows_to_ell(A, width)
    cols_t, vals_t, deg_t = _rows_to_ell(np.ascontiguousarray(A.T), width)
    return SparseFlows(cols=jnp.asarray(cols), vals=jnp.asarray(vals),
                       cols_t=jnp.asarray(cols_t), vals_t=jnp.asarray(vals_t),
                       deg=jnp.asarray(deg), deg_t=jnp.asarray(deg_t))


def to_dense(S: SparseFlows) -> Array:
    """Exact traceable inverse of :func:`from_dense` (padding adds zeros)."""
    if S.cols.ndim > 2:
        return jax.vmap(lambda cols, vals: to_dense(
            S._replace(cols=cols, vals=vals)))(S.cols, S.vals)
    n = S.n
    rows = jnp.broadcast_to(jnp.arange(n, dtype=S.cols.dtype)[:, None],
                            S.cols.shape)
    return jnp.zeros((n, n), S.vals.dtype).at[
        rows.reshape(-1), S.cols.reshape(-1)].add(S.vals.reshape(-1))


def mask_flows_sparse(S: SparseFlows, n_valid: Array) -> SparseFlows:
    """Sparse counterpart of ``qap.mask_flows``: zero every flow touching
    a padded slot (value-level masking; the stored pattern — cols, deg —
    is untouched, so shapes stay static under jit).  ``n_valid`` is a
    traceable scalar; leading batch dims on the leaves are fine."""
    w = (jnp.arange(S.n) < n_valid).astype(S.vals.dtype)
    return S._replace(vals=S.vals * w[:, None] * w[S.cols],
                      vals_t=S.vals_t * w[:, None] * w[S.cols_t])
