"""GS2 — reduction of the generalized problem to standard form.

C := U^{-T} A U^{-1}   (so A x = lambda B x  <=>  C y = lambda y, y = U x)

Two variants, exactly as discussed in the paper (Sec. 2.1):
  * ``to_standard_two_trsm``  — two triangular solves, 2 n^3 flops
    (the DTRSM path the paper found faster than DSYGST on their platform).
  * ``to_standard_sygst``     — blocked two-sided reduction exploiting
    symmetry, n^3 flops (the DSYGST path; also the PLASMA/lf+SM analogue).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .linalg_utils import symmetrize
from .looped import solve_upper

_solve_tri = jax.scipy.linalg.solve_triangular


def to_standard_two_trsm(A: jax.Array, U: jax.Array) -> jax.Array:
    """C = U^{-T} A U^{-1} via two TRSMs (2 n^3 flops)."""
    # W = U^{-T} A  : solve U^T W = A
    W = solve_upper(U, A, trans=True)
    # C = W U^{-1}  : C U = W  <=>  U^T C^T = W^T
    C = solve_upper(U, W.T, trans=True).T
    return symmetrize(C)


def _sygs2(Akk: jax.Array, Ukk: jax.Array) -> jax.Array:
    """Unblocked diagonal-block reduction: U_kk^{-T} A_kk U_kk^{-1}."""
    W = _solve_tri(Ukk, Akk, trans=1, lower=False)
    return symmetrize(_solve_tri(Ukk, W.T, trans=1, lower=False).T)


def to_standard_sygst(A: jax.Array, U: jax.Array, block: int = 256) -> jax.Array:
    """Blocked DSYGST (itype=1, upper): C = U^{-T} A U^{-1} in ~n^3 flops.

    LAPACK-style blocked sweep; per block k (ranges [k0, k1), trailing t=[k1, n)):
        A_kk   <- U_kk^{-T} A_kk U_kk^{-1}
        A_k,t  <- U_kk^{-T} A_k,t
        A_k,t  <- A_k,t - 1/2 A_kk U_k,t
        A_t,t  <- A_t,t - U_k,t^T A_k,t - A_k,t^T U_k,t     (SYR2K)
        A_k,t  <- A_k,t - 1/2 A_kk U_k,t
        A_k,t  <- A_k,t U_tt^{-1}
    """
    n = A.shape[0]
    M = A
    for k0 in range(0, n, block):
        k1 = min(k0 + block, n)
        Ukk = U[k0:k1, k0:k1]
        Ckk = _sygs2(M[k0:k1, k0:k1], Ukk)
        M = M.at[k0:k1, k0:k1].set(Ckk)
        if k1 < n:
            Ukt = U[k0:k1, k1:]
            row = _solve_tri(Ukk, M[k0:k1, k1:], trans=1, lower=False)
            row = row - 0.5 * (Ckk @ Ukt)
            # SYR2K trailing update
            Mtt = M[k1:, k1:] - Ukt.T @ row - row.T @ Ukt
            M = M.at[k1:, k1:].set(symmetrize(Mtt))
            row = row - 0.5 * (Ckk @ Ukt)
            Utt = U[k1:, k1:]
            # row <- row * U_tt^{-1}:  solve X U_tt = row  <=> U_tt^T X^T = row^T
            row = _solve_tri(Utt, row.T, trans=1, lower=False).T
            M = M.at[k0:k1, k1:].set(row)
            M = M.at[k1:, k0:k1].set(row.T)
    return symmetrize(M)
