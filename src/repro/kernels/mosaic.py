"""Building blocks the QAP kernels share, in forms Mosaic (TPU) lowers.

Mosaic rejects what interpret mode accepts in three ways that shape every
QAP kernel here:

* **Block tiling.**  The last two dims of each VMEM block must be
  divisible by (8, 128) or equal the array's own.  Permutation rows are
  therefore ``(B, 1, n_pad)`` arrays read as ``(1, 1, n_pad)`` blocks,
  per-program scalars are ``(…, 1, 1)`` blocks, and a matrix row picked
  by a scalar-prefetched index is streamed as the 8-row block that holds
  it (:func:`block_row` selects it inside the kernel).
* **No in-kernel gathers.**  ``jnp.take`` by a vector of indices does not
  lower.  A gather of a row ``x`` at positions ``idx`` is a one-hot
  matmul instead (:func:`onehot`, :func:`dot`); a scalar pick is a
  masked lane reduction (:func:`pick`).
* **Precision.**  An f32 ``dot`` at default precision runs as one bf16
  pass on the MXU.  Every dot here is a one-hot gather, which is exact
  only at ``HIGHEST`` (the f32 operand is split into bf16 parts whose sum
  reproduces it bit for bit), so that precision is pinned.
  :func:`dot_onehot` / :func:`onehot_dot` make that split themselves:
  three single-pass dots against the bf16-exact one-hot, the same bits
  in about half the MXU passes, for kernels the MXU sets the pace of.

Scalar-prefetch tables live in SMEM (1 MiB on v5e); callers chunk their
grids so a table never exceeds :data:`MAX_PREFETCH_WORDS`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANE = 128
SUBLANE = 8
HIGHEST = jax.lax.Precision.HIGHEST
MAX_PREFETCH_WORDS = 1 << 16      # 256 KiB of int32 per scalar-prefetch table


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def padded_order(n: int) -> int:
    """Kernel order: ``n`` rounded up to whole lanes (at least one)."""
    return pad_to(max(n, LANE), LANE)


def pad_perms(ps: Array, n_pad: int) -> Array:
    """(..., n) permutations -> (..., n_pad) int32, identity on the pad
    range (padded slots carry zero flow, so they never change F)."""
    n = ps.shape[-1]
    tail = jnp.broadcast_to(jnp.arange(n, n_pad, dtype=jnp.int32),
                            ps.shape[:-1] + (n_pad - n,))
    return jnp.concatenate([ps.astype(jnp.int32), tail], axis=-1)


def pad_matrix(A: Array, rows: int, cols: int) -> Array:
    """Zero-pad the last two dims of a (…, r, c) array to (rows, cols), f32."""
    widen = [(0, 0)] * (A.ndim - 2) + [(0, rows - A.shape[-2]),
                                       (0, cols - A.shape[-1])]
    return jnp.pad(A.astype(jnp.float32), widen)


def block_row(ref, r, lanes: slice = slice(None)) -> Array:
    """Row ``r % 8`` of an 8-row block ref (leading unit dims allowed) as
    a (1, L) value; ``r`` is the row's index in the whole array.

    A lane chunk (``lanes``, a static 128-aligned slice) is read as the
    whole (8, chunk) tile and the row picked by a masked sublane sum:
    Mosaic refuses a dynamic row load at a lane offset, and cannot
    relayout a lane slice of a one-row value.  The pick adds only zeros,
    so it is exact."""
    blk = ref.at[0] if len(ref.shape) == 3 else ref
    if lanes == slice(None):
        return blk[pl.ds(r % SUBLANE, 1), :]
    tile = blk[:, lanes]
    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(sub == r % SUBLANE, tile, 0), axis=0,
                   keepdims=True)


def onehot(idx: Array, rows: int) -> Array:
    """(rows, L) f32 with ``[j, l] = (idx[0, l] == j)`` for a (1, L) row
    of indices: ``dot(x, onehot(idx, n))`` gathers ``x[:, idx]``, and
    ``dot(onehot(p, n), A)`` moves row ``k`` of ``A`` to row ``p[k]``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, idx.shape[-1]), 0)
    return (iota == idx).astype(jnp.float32)


def dot(x: Array, y: Array) -> Array:
    """f32 matmul at HIGHEST precision: exact when either side is one-hot."""
    return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _bf16_parts(x: Array):
    """Three bfloat16 values whose float32 sum is ``x`` exactly: each
    takes the next 8 bits of the 24-bit significand."""
    parts = []
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return parts


def _single_pass_sum(pairs) -> Array:
    """Sum, in float32, of single-pass bfloat16 dots of ``(x, y)`` pairs."""
    out = None
    for x, y in pairs:
        d = jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def dot_onehot(x: Array, onehot_cols: Array) -> Array:
    """``x @ onehot_cols`` for a 0/1 one-hot right operand, with the same
    bits as :func:`dot` at half its MXU passes: ``x`` is split into three
    exact bfloat16 parts, each takes one single-pass dot (every output is
    one product, exact in float32), and the three are summed in float32,
    which rebuilds each gathered value exactly."""
    oh = onehot_cols.astype(jnp.bfloat16)
    return _single_pass_sum((part, oh) for part in _bf16_parts(x))


def onehot_dot(onehot_rows: Array, x: Array) -> Array:
    """``onehot_rows @ x`` for a 0/1 one-hot left operand; as
    :func:`dot_onehot`."""
    oh = onehot_rows.astype(jnp.bfloat16)
    return _single_pass_sum((oh, part) for part in _bf16_parts(x))


def stack_rows(*rows: Array) -> Array:
    """(8, L) value whose first rows are the given (1, L) rows (the rest
    repeat the last one): one MXU tile for several row gathers."""
    r = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, rows[0].shape[-1]), 0)
    out = rows[-1]
    for i in range(len(rows) - 2, -1, -1):
        out = jnp.where(r == i, rows[i], out)
    return out


def total(x: Array) -> Array:
    """Sum of a 2-D value as a (1, 1) value."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def pick(row: Array, i) -> Array:
    """``row[0, i]`` of a (1, L) f32 row as a (1, 1) value."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return total(jnp.where(lane == i, row, 0.0))


def chunks(total_count: int, per_item_words: int):
    """Split ``total_count`` grid items into ``(start, count)`` pieces
    whose scalar-prefetch tables stay under :data:`MAX_PREFETCH_WORDS`."""
    step = max(1, MAX_PREFETCH_WORDS // max(per_item_words, 1))
    return [(s, min(step, total_count - s))
            for s in range(0, total_count, step)]
