"""GS1 — Cholesky factorization B = U^T U (upper factor).

Two paths:
  * ``cholesky_upper``  — the default: XLA's fused factorization (the
    "vendor library" path; the paper's DPOTRF/MAGMA_DPOTRF analogue) where
    its compile is cheap, the looped blocked program on a TPU above one
    tile (``core.triangular.looped``).
  * ``cholesky_blocked`` — right-looking blocked algorithm (the PLASMA/lf+SM
    task-parallel analogue) as ONE ``lax.fori_loop`` over fixed-size
    diagonal tiles, so its compile does not grow with n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from .looped import (LOOP_BLOCK, cholesky_tile, looped, matmul_tiled,
                     pad_identity)


def cholesky_upper(B: jax.Array) -> jax.Array:
    """Return upper-triangular U with B = U^T U."""
    if looped(B.shape[0]):
        return cholesky_blocked(B)
    return cholesky_tile(B)


def diag_shifted(B: jax.Array, tau: float) -> jax.Array:
    """B + tau * max|diag B| * I — the GS1 breakdown-recovery shift.

    Relative to the diagonal scale so the same rung ladder (see
    ``resilience.recovery.cholesky_shift_taus``) serves pencils of any
    magnitude; the caller reports the shift it used and refinement still
    targets the original pencil."""
    n = B.shape[0]
    scale = jnp.max(jnp.abs(jnp.diagonal(B)))
    return B + (tau * scale) * jnp.eye(n, dtype=B.dtype)


def cholesky_blocked(B: jax.Array, block: int = LOOP_BLOCK) -> jax.Array:
    """Right-looking blocked Cholesky (upper factor), B = U^T U.

    for k in blocks:                      (one fori_loop step each)
        U_kk  = chol(B_kk)
        U_k,: = U_kk^{-T} B_k,:          (triangular solve on the block row)
        B_t,t = B_t,t - U_k,:^T U_k,:    (trailing update)

    B is padded to a block multiple with an identity corner; the block row
    and the trailing update span the full padded width with the finished
    columns masked out, so every step has the same shapes; the update
    (``matmul_tiled``) skips the output tiles left of the trailing window.
    """
    n = B.shape[0]
    b = min(block, n)
    nb = -(-n // b)
    N = nb * b
    cols = jnp.arange(N)[None, :]

    def step(k, carry):
        M, U = carry
        k0 = k * b
        Ukk = cholesky_tile(lax.dynamic_slice(M, (k0, k0), (b, b)))
        row = solve_triangular(Ukk, lax.dynamic_slice(M, (k0, 0), (b, N)),
                               trans=1, lower=False)
        row = jnp.where(cols >= k0 + b, row, 0)
        M = matmul_tiled(-row.T, row, M, start=k0 + b)
        row = lax.dynamic_update_slice(row, Ukk, (0, k0))
        return M, lax.dynamic_update_slice(U, row, (k0, 0))

    M = pad_identity(B, N)
    _, U = lax.fori_loop(0, nb, step, (M, jnp.zeros_like(M)))
    return U[:n, :n]
