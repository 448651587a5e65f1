"""Synthetic GSYEIG problem generators shaped like the paper's two workloads.

Both are constructed as A = U^T C U, B = U^T U with a *known* spectrum for C,
so tests have exact ground truth: the generalized eigenvalues of (A, B) are
exactly the chosen spectrum and the eigenvectors are U^{-1} Q.

  * ``md_like``  — molecular-dynamics NMA (iMod): A and B both SPD, smooth
    low-frequency end, moderate Lanczos iteration counts (paper Exp. 1).
  * ``dft_like`` — FLEUR/DFT: A symmetric indefinite-ish spectrum with a
    *clustered* lower end, B ≈ overlap matrix close to I; drives Lanczos to
    many iterations (paper Exp. 2's 4k iterations).

At the paper's sizes on a TPU the orthogonal factor is a product of
random reflectors and the products are tiled (``core.looped``), so
building a pencil compiles in the same time at any n; elsewhere XLA's QR
and matmuls run.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.looped import looped, matmul
from repro.core.precision import exact_matmuls


class GSyEigProblem(NamedTuple):
    A: jax.Array
    B: jax.Array
    exact_evals: jax.Array  # full spectrum, ascending
    name: str


#: reflectors per compact-WY block of the TPU-path orthogonal factor
_REFLECTORS = 256


@jax.jit
def _reflector_block(Q: jax.Array, key: jax.Array) -> jax.Array:
    """Q H_1 ... H_b for b reflectors H_j = I - 2 v_j v_j^T about random
    unit directions, applied as one compact-WY update Q - (Q V) T V^T
    (T from the forward recurrence of LAPACK's dlarft)."""
    n = Q.shape[0]
    b = min(_REFLECTORS, n)
    V = jax.random.normal(key, (n, b), Q.dtype)
    V = V / jnp.linalg.norm(V, axis=0, keepdims=True)
    G = V.T @ V
    idx = jnp.arange(b)

    def column(j, T):
        g = jnp.where(idx < j, G[:, j], 0)
        return T.at[:, j].set(jnp.where(idx == j, 2.0, -2.0 * (T @ g)))

    T = jax.lax.fori_loop(0, b, column, jnp.zeros((b, b), Q.dtype))
    return Q - matmul(matmul(matmul(Q, V), T), V.T)


def _random_orthogonal(n: int, key: jax.Array, dtype) -> jax.Array:
    """A random orthogonal Q. Off the TPU path: the Q of a Gaussian M = QR
    with diag(R) > 0. On it: a product of n random reflectors in blocks —
    orthogonal to rounding by construction, from GEMMs alone. (CholeskyQR
    of the Gaussian M, plain or shifted, left max|Q^T Q - I| ~ 2e-6 at
    n=9,997 on a v5e, which shifts the "exact" spectrum by as much.)"""
    if looped(n):
        Q = jnp.eye(n, dtype=dtype)
        for i in range(-(-n // _REFLECTORS)):
            Q = _reflector_block(Q, jax.random.fold_in(key, i))
        return Q
    M = jax.random.normal(key, (n, n), dtype)
    Q, R = jnp.linalg.qr(M)
    # fix signs for determinism
    return Q * jnp.sign(jnp.diagonal(R))[None, :]


@exact_matmuls
def _assemble(n: int, spectrum: jax.Array, key: jax.Array, dtype,
              b_offdiag: float, name: str) -> GSyEigProblem:
    kq, ku = jax.random.split(key)
    Q = _random_orthogonal(n, kq, dtype)
    C = matmul(Q * spectrum[None, :], Q.T)
    C = 0.5 * (C + C.T)
    # U = I + small strictly-upper noise: B = U^T U is SPD, well conditioned
    noise = jax.random.normal(ku, (n, n), dtype) * (b_offdiag / jnp.sqrt(n))
    U = jnp.eye(n, dtype=dtype) + jnp.triu(noise, k=1)
    A = matmul(matmul(U.T, C), U)
    A = 0.5 * (A + A.T)
    B = matmul(U.T, U)
    B = 0.5 * (B + B.T)
    return GSyEigProblem(A=A, B=B, exact_evals=jnp.sort(spectrum), name=name)


def md_like(n: int, key: jax.Array | None = None,
            dtype=jnp.float64) -> GSyEigProblem:
    """Both A, B SPD; spectrum spans ~4 decades, smooth low end (NMA modes)."""
    if key is None:
        key = jax.random.PRNGKey(9997)
    kq, ks = jax.random.split(key)
    # positive spectrum, log-spaced + jitter: lowest modes well separated
    base = jnp.logspace(-2.0, 2.0, n, dtype=dtype)
    jitter = 1.0 + 0.01 * jax.random.uniform(ks, (n,), dtype)
    spectrum = base * jitter
    return _assemble(n, spectrum, kq, dtype, b_offdiag=0.3, name="md")


def dft_like(n: int, key: jax.Array | None = None,
             dtype=jnp.float64) -> GSyEigProblem:
    """Symmetric A (negative + positive), tight cluster at the low end; B≈I.

    The clustered valence band means slow Lanczos convergence — this is what
    produced the paper's 4k-iteration counts in Experiment 2.
    """
    if key is None:
        key = jax.random.PRNGKey(17243)
    kq, ks = jax.random.split(key)
    n_low = max(n // 10, 4)
    # low cluster: tightly spaced "valence" states
    low = -1.0 + 0.02 * jnp.arange(n_low, dtype=dtype) / n_low
    # the rest: spread "conduction" states
    high = jnp.linspace(0.0, 50.0, n - n_low, dtype=dtype)
    spectrum = jnp.concatenate([low, high])
    jitter = 1.0 + 1e-3 * jax.random.uniform(ks, (n,), dtype)
    spectrum = spectrum * jitter
    return _assemble(n, spectrum, kq, dtype, b_offdiag=0.1, name="dft")


def paper_shapes() -> dict:
    """The paper's two experiment sizes (for --full benchmark runs)."""
    return {
        "md": dict(n=9_997, s=100),
        "dft": dict(n=17_243, s=448),
    }
