"""Blocked TRSM driver: Pallas diagonal-tile solves + Pallas GEMM updates.

Solves U X = B (``trans=False``) or U^T X = B (``trans=True``) for upper
triangular U — the exact operations behind the paper's GS2/BT1/KI stages.
The block loop runs at trace time (static shapes per step); the O(n^2 s)
GEMM updates dominate and run on the MXU path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from ..gemm.ops import gemm
from .kernel import trsm_tile
from .ref import trsm_ref


@functools.partial(jax.jit, static_argnames=("trans", "block",
                                             "force_interpret"))
def trsm(U: jax.Array, B: jax.Array, trans: bool = False, block: int = 128,
         force_interpret: bool | None = None) -> jax.Array:
    """Blocked triangular solve; B may be (n,) or (n, s)."""
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    n, s = B.shape
    if not dispatch.use_pallas(U.dtype, force=True):
        X = trsm_ref(U, B, trans=trans)
        return X[:, 0] if squeeze else X
    interpret = dispatch.interpret(force_interpret)
    block = min(block, n)
    X = jnp.zeros_like(B)
    blocks = [(k0, min(k0 + block, n)) for k0 in range(0, n, block)]
    if trans:
        # forward over block rows: U^T lower triangular
        for (k0, k1) in blocks:
            rhs = B[k0:k1, :]
            if k0 > 0:
                # rhs -= U[0:k0, k0:k1]^T X[0:k0]
                rhs = rhs - gemm(U[:k0, k0:k1].T, X[:k0, :],
                                 force_interpret=force_interpret)
            Xk = trsm_tile(U[k0:k1, k0:k1], rhs, trans=True,
                           interpret=interpret)
            X = X.at[k0:k1, :].set(Xk)
    else:
        # backward over block rows
        for (k0, k1) in reversed(blocks):
            rhs = B[k0:k1, :]
            if k1 < n:
                rhs = rhs - gemm(U[k0:k1, k1:], X[k1:, :],
                                 force_interpret=force_interpret)
            Xk = trsm_tile(U[k0:k1, k0:k1], rhs, trans=False,
                           interpret=interpret)
            X = X.at[k0:k1, :].set(Xk)
    return X[:, 0] if squeeze else X


__all__ = ["trsm", "trsm_ref"]
