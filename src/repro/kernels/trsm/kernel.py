"""Pallas TPU kernels for the blocked triangular solve with multiple RHS.

The paper's GS2 (two DTRSMs, its chosen path over DSYGST), BT1, and the KI
per-iteration solves all hinge on TRSM. A TPU-native TRSM splits into

  (a) a *diagonal-tile* solve — inherently sequential over the b rows of the
      tile; done in-kernel with a VPU forward/back-substitution fori_loop
      over a (b, b) tile held entirely in VMEM, and
  (b) MXU GEMM updates B_i := B_i - U_ik^T X_k — which dominate the flops
      (BLAS-3) and are the gemm kernel's job at the ops.py layer.

Both tile solves (U X = B and U^T X = B) are provided. b defaults to 128:
the substitution loop is latency-bound so small tiles keep it short while
the (128, s)-tile updates still feed the MXU full faces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import I0


def _trsm_tile_upper_kernel(u_ref, b_ref, x_ref):
    """Solve U X = B for one (b, b) upper-triangular tile, RHS (b, s).

    Backward substitution: x_i = (b_i - sum_{j>i} U_ij x_j) / U_ii.
    """
    U = u_ref[...]
    B = b_ref[...]
    b = U.shape[0]

    def body(k, X):
        i = b - 1 - k
        # contributions of already-solved rows (> i)
        row = U[i, :]  # (b,)
        mask = (jnp.arange(b) > i).astype(U.dtype)
        acc = (mask * row) @ X  # (s,)
        xi = (B[i, :] - acc) / U[i, i]
        return X.at[i, :].set(xi)

    X = jax.lax.fori_loop(0, b, body, jnp.zeros_like(B))
    x_ref[...] = X


def _trsm_tile_upper_t_kernel(u_ref, b_ref, x_ref):
    """Solve U^T X = B for one (b, b) upper-triangular tile (forward subst)."""
    U = u_ref[...]
    B = b_ref[...]
    b = U.shape[0]

    def body(i, X):
        col = U[:, i]  # U^T row i = U column i
        mask = (jnp.arange(b) < i).astype(U.dtype)
        acc = (mask * col) @ X
        xi = (B[i, :] - acc) / U[i, i]
        return X.at[i, :].set(xi)

    X = jax.lax.fori_loop(0, b, body, jnp.zeros_like(B))
    x_ref[...] = X


@functools.partial(jax.jit, static_argnames=("trans", "interpret"))
def trsm_tile(U: jax.Array, B: jax.Array, trans: bool = False,
              interpret: bool = True) -> jax.Array:
    """Single-tile triangular solve as a Pallas call (whole tile in VMEM)."""
    b, s = B.shape
    kern = _trsm_tile_upper_t_kernel if trans else _trsm_tile_upper_kernel
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, b), lambda i: (I0, I0)),
            pl.BlockSpec((b, s), lambda i: (I0, I0)),
        ],
        out_specs=pl.BlockSpec((b, s), lambda i: (I0, I0)),
        out_shape=jax.ShapeDtypeStruct((b, s), B.dtype),
        interpret=interpret,
    )(U, B)
