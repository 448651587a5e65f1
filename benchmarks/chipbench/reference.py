"""The comparison that decides ``correct``.

Each solve's eigenpairs are held against the pencil they were asked of:
the eigenvalues against the spectrum known from the pencil's
construction, the eigenvectors through the two accuracy quantities of
the paper's Tables 3 and 7, against the original A and B:

  b_orthogonality   = ||I - X^T B X||_F / ||B||_F
  relative_residual = ||A X - B X diag(lam)||_F / max(||A||_F, ||B||_F)
  eval_error        = max_i |lam_i - exact_i| / max(||A||_F, ||B||_F)

The first two copy the arithmetic of the program's
``core.residuals.accuracy_report``; the eigenvalue error is normalized
as the residual is (relative to the wanted eigenvalues themselves, the
f64 products that build the pencil already perturb its smallest
eigenvalues by ~2e-10 absolute). Products are HIGHEST precision and f64
ones tiled (``products``). Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.products import dot, matmul


@jax.jit
def _accuracy(A: jax.Array, B: jax.Array, X: jax.Array, lam: jax.Array):
    AX = matmul(A, X)
    BX = matmul(B, X)
    G = dot(X.T, BX)
    scale = jnp.maximum(jnp.linalg.norm(A), jnp.linalg.norm(B))
    orth = (jnp.linalg.norm(G - jnp.eye(X.shape[1], dtype=X.dtype))
            / jnp.linalg.norm(B))
    resid = jnp.linalg.norm(AX - BX * lam[None, :]) / scale
    return orth, resid, scale


def wanted(exact: np.ndarray, s: int, which: str) -> np.ndarray:
    """The s wanted eigenvalues of an ascending spectrum, ascending."""
    if which == "smallest":
        return exact[:s]
    if which == "largest":
        return exact[-s:]
    raise ValueError(f"which must be 'smallest' or 'largest', got {which!r}")


def accuracy(A, B, X, lam, exact, which: str) -> dict:
    """The three numbers of one solve (eigenvalues ascending)."""
    lam = jnp.asarray(lam, A.dtype)
    orth, resid, scale = (float(v) for v in
                          jax.device_get(_accuracy(A, B, X, lam)))
    lam_h = np.asarray(jax.device_get(lam), np.float64)
    want = wanted(np.asarray(jax.device_get(exact), np.float64),
                  lam_h.shape[0], which)
    err = float(np.max(np.abs(np.sort(lam_h) - want))) / scale
    return {"eval_error": err, "relative_residual": resid,
            "b_orthogonality": orth}
