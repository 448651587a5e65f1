"""TD3/TT4/BT1 — back-transforms from T-space to the generalized problem.

  TD3:  Y := Q Z   (apply factored Householder reflectors — DORMTR)
  TT4:  Y := (Q1 Q2) Z  (single GEMM with the explicitly accumulated Q)
  BT1:  X := U^{-1} Y  (triangular solve — DTRSM)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .looped import solve_upper


def back_transform_generalized(U: jax.Array, Y: jax.Array) -> jax.Array:
    """BT1: X = U^{-1} Y, the final map from STDEIG to GSYEIG eigenvectors."""
    return solve_upper(U, Y)


def forward_transform_generalized(U: jax.Array, X: jax.Array) -> jax.Array:
    """Y = U X (inverse of BT1), used by tests and restart bootstrapping."""
    return jnp.triu(U) @ X
