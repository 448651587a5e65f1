"""Accuracy metrics — exactly the two quantities of the paper's Tables 3/7.

  orth  = || I - X^T B X ||_F / || B ||_F
  resid = || A X - B X Lambda ||_F / max(||A||_F, ||B||_F)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .looped import matmul
from .precision import exact_matmuls


class AccuracyReport(NamedTuple):
    b_orthogonality: jax.Array
    relative_residual: jax.Array


def b_orthogonality(X: jax.Array, B: jax.Array) -> jax.Array:
    s = X.shape[1]
    G = X.T @ matmul(B, X)
    return jnp.linalg.norm(G - jnp.eye(s, dtype=X.dtype)) / jnp.linalg.norm(B)


def relative_residual(A: jax.Array, B: jax.Array, X: jax.Array,
                      lam: jax.Array) -> jax.Array:
    R = matmul(A, X) - matmul(B, X) * lam[None, :]
    denom = jnp.maximum(jnp.linalg.norm(A), jnp.linalg.norm(B))
    return jnp.linalg.norm(R) / denom


@exact_matmuls
def accuracy_report(A: jax.Array, B: jax.Array, X: jax.Array,
                    lam: jax.Array) -> AccuracyReport:
    return AccuracyReport(
        b_orthogonality=b_orthogonality(X, B),
        relative_residual=relative_residual(A, B, X, lam),
    )


def b_normalize(X: jax.Array, B: jax.Array) -> jax.Array:
    """Scale columns of X to unit B-norm (x^T B x = 1)."""
    nrm = jnp.sqrt(jnp.maximum(jnp.einsum("is,is->s", X, matmul(B, X)),
                               jnp.finfo(X.dtype).tiny))
    return X / nrm[None, :]
