"""Pallas TPU kernel: leading-batch QAP objective evaluation.

The GA hot loop: every new descendant needs a full O(N^2) objective
re-evaluation (the paper, S5, cites this as the GA's cost driver).  On TPU we
adapt the CPU gather loop to the MXU.  With ``P^T = onehot(p)``
(``P^T[j, l] = [p[l] == j]``), ``M @ P^T`` holds ``M[i, p[l]]`` and
``P^T @ C`` moves row ``k`` of ``C`` to row ``p[k]``, so

    F(p) = sum_{k,l} C[k,l] M[p[k], p[l]] = sum((M @ P^T) * (P^T @ C))

-- two N x N one-hot matmuls on the systolic array (exact at HIGHEST
precision, ``kernels/mosaic.py``), an elementwise product and a reduction.

``qap_objective_pallas_batch`` is the wide-generation entry point: perms
``(B, P, N)`` evaluate in **one** launch whose grid spans every
(leading-dim, permutation) pair -- the GA's (islands x offspring) set per
generation, or (instances x islands x offspring) for the batched solvers
(``C``/``M`` may then carry the leading instance axis themselves).
``qap_objective_pallas`` is the lead-free wrapper, the same pattern as
``qap_delta_pallas`` / ``qap_delta_pallas_batch``.  The dispatch layer
(``ops.qap_objective``) folds any outer ``vmap`` axes into the leading
grid axis, so the kernel never runs under ``vmap``.

VMEM per program: C and M (single-buffered when shared, double-buffered
when they change with the instance) plus the one-hot and product
temporaries, 2.25 MiB each at the cap.  Compiled for v5e at n_pad = 768
the kernel needs 23.45 MiB of scoped VMEM with shared matrices and
27.95 MiB with instance-batched ones (the compiler's own figures), above
the 16 MiB default, so it raises the limit to :data:`VMEM_LIMIT_BYTES`.
Orders above ``MAX_KERNEL_N`` fall back to the reference implementation
(ops.py).

Padding: matrices are zero-padded to a multiple of 128 (MXU lane width);
permutations are padded with the identity on the pad range, and since the
padded rows/cols of C are zero they contribute nothing to F.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic

Array = jax.Array

MAX_KERNEL_N = 768  # padded-N cap so the working set fits VMEM
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _objective_kernel(p_ref, c_ref, m_ref, out_ref, *, n_pad: int):
    """One program instance == one (leading-dim, permutation) pair."""
    pt = mosaic.onehot(p_ref[0], n_pad)               # (n_pad, n_pad)
    # With batched matrices the block carries a leading length-1 instance dim.
    M = m_ref[0] if len(m_ref.shape) == 3 else m_ref[...]
    C = c_ref[0] if len(c_ref.shape) == 3 else c_ref[...]
    out_ref[0] = mosaic.total(mosaic.dot(M, pt) * mosaic.dot(pt, C))


def matrix_spec(n_pad: int, batched: bool, instance_of):
    """BlockSpec of a whole (n_pad, n_pad) matrix per program: shared
    matrices are fetched once and single-buffered; instance-batched ones
    follow ``instance_of(*grid_indices) -> instance``."""
    if batched:
        return pl.BlockSpec((1, n_pad, n_pad),
                            lambda *g: (instance_of(*g), 0, 0))
    return pl.BlockSpec((n_pad, n_pad), lambda *g: (0, 0),
                        pipeline_mode=pl.Buffered(1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_objective_pallas_batch(C: Array, M: Array, perms: Array,
                               interpret: bool = False) -> Array:
    """Leading-batch objective on TPU: one grid over every permutation.

    perms: (B, P, N) -> (B, P) f32; the grid is (B * P,), one program per
    (leading-dim, permutation) pair.  C, M are either shared ``(N, N)``
    matrices or instance-batched ``(B, N, N)`` (the batched solvers' case,
    where leading dim b of ``perms`` belongs to instance b).
    """
    n = perms.shape[-1]
    b, p_cnt = perms.shape[0], perms.shape[1]
    mat_batched = C.ndim == 3
    if mat_batched and C.shape[0] != b:
        raise ValueError(
            f"batched C/M leading dim {C.shape[0]} != perms leading dim {b}")
    n_pad = mosaic.padded_order(n)
    if n_pad > MAX_KERNEL_N:
        raise ValueError(f"padded N={n_pad} exceeds kernel cap {MAX_KERNEL_N}")

    Cp = mosaic.pad_matrix(C, n_pad, n_pad)
    Mp = mosaic.pad_matrix(M, n_pad, n_pad)
    pp = mosaic.pad_perms(perms.reshape(b * p_cnt, 1, n), n_pad)
    mat_spec = matrix_spec(n_pad, mat_batched, lambda i: i // p_cnt)
    out = pl.pallas_call(
        functools.partial(_objective_kernel, n_pad=n_pad),
        name="qap_objective",
        grid=(b * p_cnt,),
        in_specs=[
            pl.BlockSpec((1, 1, n_pad), lambda i: (i, 0, 0)),    # this perm
            mat_spec,                                            # C
            mat_spec,                                            # M
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * p_cnt, 1, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(pp, Cp, Mp)
    return out.reshape(b, p_cnt)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qap_objective_pallas(C: Array, M: Array, perms: Array,
                         interpret: bool = False) -> Array:
    """Lead-free wrapper.  C, M: (N, N); perms: (B, N) -> (B,) f32."""
    return qap_objective_pallas_batch(C, M, perms[None],
                                      interpret=interpret)[0]
