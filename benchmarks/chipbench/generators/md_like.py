"""``md_like``: a pencil shaped like the paper's iMod normal-mode problem.

A copy of the program's ``repro.data.problems.md_like`` on its
reflector path, kept with the benchmark so that a change to the
program's generator cannot move the yardstick. A = U^T C U and
B = U^T U with C = Q diag(spectrum) Q^T, so the generalized eigenvalues
of (A, B) are exactly the spectrum:

- spectrum: log-spaced over [1e-2, 1e2] times (1 + 0.01 u), u uniform —
  A and B both SPD, a smooth, well separated low end;
- Q: a product of n random reflectors in compact-WY blocks of 256,
  orthogonal to rounding by construction from GEMMs alone (a CholeskyQR
  factor left max|Q^T Q - I| ~ 2e-6 at n=9,997 on a v5e);
- U = I + strictly upper Gaussian noise of scale 0.3 / sqrt(n), so B is
  well conditioned.

The whole pencil is one jitted program from the key, its f64 products
tiled (``products.matmul``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.products import dot, matmul

#: reflectors per compact-WY block of the orthogonal factor
REFLECTORS = 256
#: scale of U's strictly upper noise, times 1 / sqrt(n)
B_OFFDIAG = 0.3


class Pencil(NamedTuple):
    A: jax.Array
    B: jax.Array
    exact_evals: jax.Array  # the whole spectrum, ascending


def _reflector_block(Q: jax.Array, key: jax.Array) -> jax.Array:
    """Q H_1 ... H_b for b reflectors H_j = I - 2 v_j v_j^T about random
    unit directions, as one compact-WY update Q - (Q V) T V^T (T from the
    forward recurrence of LAPACK's dlarft)."""
    n = Q.shape[0]
    b = min(REFLECTORS, n)
    V = jax.random.normal(key, (n, b), Q.dtype)
    V = V / jnp.linalg.norm(V, axis=0, keepdims=True)
    G = dot(V.T, V)
    idx = jnp.arange(b)

    def column(j, T):
        g = jnp.where(idx < j, G[:, j], 0)
        return T.at[:, j].set(jnp.where(idx == j, 2.0, -2.0 * dot(T, g)))

    T = lax.fori_loop(0, b, column, jnp.zeros((b, b), Q.dtype))
    return Q - matmul(matmul(matmul(Q, V), T), V.T)


def _random_orthogonal(n: int, key: jax.Array, dtype) -> jax.Array:
    blocks = -(-n // REFLECTORS)
    return lax.fori_loop(
        0, blocks,
        lambda i, Q: _reflector_block(Q, jax.random.fold_in(key, i)),
        jnp.eye(n, dtype=dtype))


@partial(jax.jit, static_argnums=(0, 2))
def build(n: int, key: jax.Array, dtype=jnp.float64) -> Pencil:
    """The pencil of size n drawn from ``key``."""
    kq, ks = jax.random.split(key)
    base = jnp.logspace(-2.0, 2.0, n, dtype=dtype)
    spectrum = base * (1.0 + 0.01 * jax.random.uniform(ks, (n,), dtype))
    kq, ku = jax.random.split(kq)
    Q = _random_orthogonal(n, kq, dtype)
    C = matmul(Q * spectrum[None, :], Q.T)
    C = 0.5 * (C + C.T)
    noise = jax.random.normal(ku, (n, n), dtype) * (B_OFFDIAG / jnp.sqrt(n))
    U = jnp.eye(n, dtype=dtype) + jnp.triu(noise, k=1)
    A = matmul(matmul(U.T, C), U)
    A = 0.5 * (A + A.T)
    B = matmul(U.T, U)
    B = 0.5 * (B + B.T)
    return Pencil(A=A, B=B, exact_evals=jnp.sort(spectrum))
