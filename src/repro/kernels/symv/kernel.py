"""Pallas TPU kernel: symmetric matrix times an (n, p) block, reading only
the UPPER triangle (a symv is its p = 1 case).

The paper's KE1 (CUBLAS/MAGMA DSYMV) is the hot loop of the Krylov solver. On
TPU a symv is HBM-bandwidth-bound (2 flops per element read), so the win the
paper gets from exploiting symmetry in *flops* becomes a win in *bytes* here:
each upper-triangle tile A_ij is streamed through VMEM once and contributes

    y_up[i] += A_ij @ x[j]          (its own row block)
    y_lo[j] += A_ij^T @ x[i]        (the mirrored row block, j > i)

halving HBM traffic vs a dense gemv. The grid enumerates the nb(nb+1)/2
upper-triangle tiles via scalar-prefetched (ib, jb) index arrays
(PrefetchScalarGridSpec), row-major so y_up accumulates contiguously.

VMEM budget per step: the (block, block) tile plus four (block, p) slices;
with block = 512, p = 128 and f32 that is ~2 MiB << 16 MiB v5e VMEM,
leaving room for double buffering. Tile dims are multiples of (8, 128) as
the VPU/MXU want.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.dispatch import I0
from jax.experimental.pallas import tpu as pltpu


def _symv_kernel(ib, jb, a_ref, xj_ref, xi_ref, yu_ref, yl_ref):
    t = pl.program_id(0)
    i = ib[t]
    j = jb[t]

    a = a_ref[...]
    # the output refs double as cross-tile accumulators; for bf16 operands
    # the wrappers allocate them in fp32 (the MXU accumulator dtype) and
    # preferred_element_type pins every per-tile contraction to match
    acc_t = yu_ref.dtype

    def dot(m, v):
        return jnp.dot(m, v, preferred_element_type=acc_t)

    # --- diagonal tile: only its upper triangle is semantic. Mask in-register
    # and fold in its own mirror: y_up[i] = triu(A_ii) x_i + striu(A_ii)^T x_i.
    # i == j is the first step of each contiguous i-run => acts as the init.
    @pl.when(i == j)
    def _diag():
        rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        a_up = jnp.where(rows <= cols, a, 0)
        a_strict = jnp.where(rows < cols, a, 0)
        yu_ref[...] = dot(a_up, xj_ref[...]) + dot(a_strict.T, xj_ref[...])

    # --- strictly-upper tile: y_up[i] += A_ij x_j
    @pl.when(j > i)
    def _off():
        yu_ref[...] += dot(a, xj_ref[...])

    # --- mirrored contribution: y_lo[j] += A_ij^T x_i (strictly upper only).
    # Every j-block's first visit is at i == 0 (row-major triangle order), so
    # initialization there covers all blocks, including j == 0 (no strictly-
    # upper tile) which must come out zero.
    @pl.when(i == 0)
    def _init_lo():
        yl_ref[...] = jnp.zeros_like(yl_ref)

    @pl.when(j > i)
    def _acc_lo():
        yl_ref[...] += dot(a.T, xi_ref[...])




def triangle_indices(nb: int):
    """Row-major upper-triangle (i, j >= i) block index arrays."""
    pairs = [(i, j) for i in range(nb) for j in range(i, nb)]
    ib = np.asarray([p[0] for p in pairs], np.int32)
    jb = np.asarray([p[1] for p in pairs], np.int32)
    return ib, jb


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def symm_block_pallas(A: jax.Array, X: jax.Array, block: int = 512,
                      interpret: bool = True) -> jax.Array:
    """Y = A X for symmetric A and an (n, p) block of RHS vectors, reading
    only the upper triangle of A — the fused multi-RHS matvec of the block
    Lanczos core (KE1 over a whole s-step block in ONE kernel pass).

    The kernel body is exactly ``_symv_kernel``: every tile contribution is
    a (block, block) @ (block, p) matmul instead of a mat-vec, so the same
    one-triangle streaming halves HBM traffic while the MXU amortizes the
    tile read over p right-hand sides (arithmetic intensity grows p-fold —
    this is what makes the block method compute- rather than
    bandwidth-bound). Requires n % block == 0 (ops.py pads); p rides along
    unblocked (ops.py pads it to the lane granularity on a real TPU).
    """
    n = A.shape[0]
    p = X.shape[1]
    assert n % block == 0, (n, block)
    nb = n // block
    ib, jb = triangle_indices(nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(ib),),
        in_specs=[
            pl.BlockSpec((block, block), lambda t, ib, jb: (ib[t], jb[t])),
            pl.BlockSpec((block, p), lambda t, ib, jb: (jb[t], I0)),
            pl.BlockSpec((block, p), lambda t, ib, jb: (ib[t], I0)),
        ],
        out_specs=[
            pl.BlockSpec((block, p), lambda t, ib, jb: (ib[t], I0)),
            pl.BlockSpec((block, p), lambda t, ib, jb: (jb[t], I0)),
        ],
    )
    acc_t = jnp.float32 if A.dtype == jnp.bfloat16 else A.dtype
    y_up, y_lo = pl.pallas_call(
        _symv_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, p), acc_t)] * 2,
        interpret=interpret,
    )(jnp.asarray(ib), jnp.asarray(jb), A, X, X)
    return (y_up + y_lo).astype(A.dtype)
