"""fp64 iterative refinement of approximate generalized eigenpairs.

Closes the mixed-precision loop: the reduced-precision pipeline returns
eigenpair estimates of ``A X = B X Lambda`` that are accurate to roughly
the compute dtype's epsilon; this module refines them against the
*original fp64 pencil* until the Table-3 tolerances are met.

The method is correction-form subspace inverse iteration with a single
shared shift and a guard buffer:

  1. pick sigma strictly outside the wanted end of the spectrum and
     factor ``A - sigma B`` ONCE — in fp32 (the classic mixed-precision
     refinement split: the factorization is only a preconditioner, the
     residuals that drive convergence are fp64, so the error contracts
     multiplicatively and the fp32 factor costs half an fp64 LU);
  2. widen the s returned columns with a few random *guard* columns:
     the guards converge to the next-nearest eigenvectors and deflate
     them, moving the per-step contraction of pair i from
     ``|lam_i - sigma| / |lam_{s+1} - sigma|`` to
     ``|lam_i - sigma| / |lam_{q+1} - sigma|`` — decisive when the
     wanted end has tight relative gaps (the MD-like log spectrum);
  3. per step (all fp64 except the triangular solves):
     ``R = A X - B X diag(lam)``, ``X <- X - (A - sigma B)^{-1} R``,
     B-orthonormalize by Cholesky-QR, Rayleigh-Ritz on the fp64 pencil;
  4. stop when ``relative_residual`` and ``b_orthogonality`` (the exact
     Table-3 metrics of ``core.residuals``) are under tolerance on the
     wanted s pairs.

Eigenvalues are corrected quadratically by the Rayleigh-Ritz step, and
near-cluster contamination contributes residual only in proportion to
the (tiny) eigenvalue gap, so the *metrics* converge in a handful of
steps even for the DFT-like clustered spectra.

``refine_eigenpairs`` is the host-loop driver (early exit, trajectory
recording) used by ``gsyeig.solve``; ``refine_eigenpairs_fixed`` is the
traceable fixed-step variant the vmapped ``core.batched`` pipelines
fuse into their compiled programs.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import lu_factor, lu_solve, solve_triangular

from .instrument import fetch

# the shared Table-3 tolerance (tests/test_accuracy_harness.py asserts
# both metrics against this same value)
REFINE_TOL = 1e-12


def default_guard(s: int, n: int) -> int:
    """Guard-buffer width: enough deflation to matter, still O(s) cost.

    Sized ~3x the wanted count: on the MD-like log spectrum each extra
    deflated neighbor improves the per-step contraction by the local
    eigenvalue ratio, and tripling the buffer roughly squares the rate —
    fewer (n^2 q)-cost sweeps beat a narrower q per sweep."""
    return max(0, min(max(8, 3 * s), 32, n - s))


def _sigma(lam, which: str):
    """Shift strictly outside the wanted end of the spectrum.

    The margin is half the wanted-set spread plus a scale-aware floor so
    an eigenvalue-estimate error cannot land sigma on top of a true
    eigenvalue (a singular factorization). Estimates from a demoted
    pipeline can be off by ~eps_compute * ||C|| in absolute terms, which
    makes the *initial* sigma far from the wanted end and the contraction
    slow — the host driver below re-shifts and refactors as soon as the
    Rayleigh-Ritz values (which converge much faster than the vectors)
    imply a materially better shift.
    """
    lo, hi = jnp.min(lam), jnp.max(lam)
    scale = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
    # keep the margin SMALL relative to the wanted-set spread: when the
    # wanted end spans a wide range (the log-spectrum largest end) the
    # contraction ratio degrades with every unit of shift-to-end distance,
    # and a shift that drifts slightly inside the spectrum is harmless —
    # the nearest eigenpairs are exactly the wanted + guarded ones
    margin = 0.05 * (hi - lo) + 0.01 * scale
    margin = jnp.maximum(margin, 1e-6 * (1.0 + scale))
    if which == "smallest":
        return lo - margin
    return hi + margin


@jax.jit
def _factor_f32(A, B, sigma):
    """fp32 LU of the shifted pencil (partial pivoting; indefinite is fine)."""
    K = (A - sigma * B).astype(jnp.float32)
    return lu_factor(K)


def _refine_step(lu, piv, A, B, lam, X):
    """One fp64 correction + Cholesky-QR B-orthonormalization + RR step."""
    R = A @ X - (B @ X) * lam[None, :]
    D = lu_solve((lu, piv), R.astype(jnp.float32)).astype(jnp.float64)
    Y = X - D
    # column equilibration before the Gram matrix (the inverse-iteration
    # map amplifies near-shift directions; keep the Cholesky-QR tame)
    Y = Y / jnp.maximum(jnp.linalg.norm(Y, axis=0), jnp.finfo(Y.dtype).tiny)
    G = Y.T @ (B @ Y)
    G = 0.5 * (G + G.T)
    L = jnp.linalg.cholesky(G)
    Z = solve_triangular(L, Y.T, lower=True).T
    H = Z.T @ (A @ Z)
    H = 0.5 * (H + H.T)
    lam, S = jnp.linalg.eigh(H)
    return lam, Z @ S


_jit_refine_step = jax.jit(_refine_step)


def _select(lam, X, s: int, which: str):
    """The wanted s of the q refined pairs (RR order is ascending)."""
    if which == "smallest":
        return lam[:s], X[:, :s]
    return lam[-s:], X[:, -s:]


@partial(jax.jit, static_argnames=("s", "which"))
def _metrics(A, B, lam, X, s: int, which: str):
    from .residuals import b_orthogonality, relative_residual
    lam_s, X_s = _select(lam, X, s, which)
    return (relative_residual(A, B, X_s, lam_s),
            b_orthogonality(X_s, B))


def _with_guards(lam, X, guard: int, which: str, key):
    """Append `guard` random columns (and end-value Ritz placeholders —
    the correction step's per-column shift only scales the column, so any
    finite value works; the first RR replaces them)."""
    if guard <= 0:
        return lam, X
    n = X.shape[0]
    G = jax.random.normal(key, (n, guard), X.dtype)
    G = G / jnp.linalg.norm(G, axis=0)
    end = lam[0] if which == "largest" else lam[-1]
    pad = jnp.full((guard,), end, lam.dtype)
    if which == "largest":
        return jnp.concatenate([pad, lam]), jnp.concatenate([G, X], axis=1)
    return jnp.concatenate([lam, pad]), jnp.concatenate([X, G], axis=1)


def refine_eigenpairs(
    A: jax.Array,
    B: jax.Array,
    lam: jax.Array,
    X: jax.Array,
    which: str = "smallest",
    *,
    tol: float = REFINE_TOL,
    max_steps: int = 60,
    guard: int | None = None,
    key: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, Any]]:
    """Refine (lam, X) against the fp64 pencil until Table-3 tolerances.

    Returns ``(lam, X, info)`` with ``info`` recording the step count and
    the full residual / B-orthogonality trajectories (index 0 is the
    unrefined input) — this is what lands in ``result.info['refinement']``.
    """
    A = jnp.asarray(A, jnp.float64)
    B = jnp.asarray(B, jnp.float64)
    lam = jnp.asarray(lam, jnp.float64)
    X = jnp.asarray(X, jnp.float64)
    s = X.shape[1]
    if guard is None:
        guard = default_guard(s, A.shape[0])
    if key is None:
        key = jax.random.PRNGKey(1203)

    sigma = float(fetch(_sigma(lam, which)))
    lu, piv = _factor_f32(A, B, sigma)

    resid, orth = map(float, fetch(_metrics(A, B, lam, X, s=s,
                                            which="smallest")))
    resid_traj = [resid]
    orth_traj = [orth]
    lam_q, X_q = _with_guards(lam, X, guard, which, key)
    steps = 0
    stalled = 0
    refactors = 0
    sigmas = [sigma]
    finite = True
    while (resid_traj[-1] > tol or orth_traj[-1] > tol) and steps < max_steps:
        lam_new, X_new = _jit_refine_step(lu, piv, A, B, lam_q, X_q)
        r, o = map(float, fetch(_metrics(A, B, lam_new, X_new, s=s,
                                         which=which)))
        if not (np.isfinite(r) and np.isfinite(o)):
            finite = False
            break                      # degenerate input; keep the last good
        lam_q, X_q = lam_new, X_new
        resid_traj.append(r)
        orth_traj.append(o)
        steps += 1
        if r <= tol and o <= tol:
            break
        lam_s, _ = _select(lam_q, X_q, s, which)
        end, sig2 = map(float, fetch((
            lam_s[0] if which == "smallest" else lam_s[-1],
            _sigma(lam_s, which))))
        if (refactors < 3
                and abs(sig2 - sigma) > 0.25 * abs(end - sigma)):
            # the Ritz values moved enough that a fresh shift contracts
            # materially faster — refactor (another half-fp64-LU, cheap
            # next to the steps it saves)
            sigma = sig2
            lu, piv = _factor_f32(A, B, sigma)
            sigmas.append(sigma)
            refactors += 1
            stalled = 0
            continue
        # three consecutive non-improving steps means we are at the fp64
        # attainable floor (or the shift cannot contract further) — stop
        # rather than spin
        stalled = stalled + 1 if r >= 0.95 * resid_traj[-2] else 0
        if stalled >= 3:
            break

    if steps > 0:
        lam, X = _select(lam_q, X_q, s, which)
    info = {
        "steps": steps,
        "sigma": sigmas,
        "guard": int(guard),
        "tol": float(tol),
        "converged": bool(resid_traj[-1] <= tol and orth_traj[-1] <= tol),
        "relative_residual": resid_traj,
        "b_orthogonality": orth_traj,
        # the degradation ladder's inputs (resilience.recovery): a stall
        # above tolerance on a demoted pipeline escalates to fp64, a
        # non-finite trajectory is a diagnosed health failure
        "stalled": bool(stalled >= 3),
        "finite": bool(finite),
    }
    return lam, X, info


@partial(jax.jit, static_argnames=("which", "steps", "guard"))
def refine_eigenpairs_fixed(
    A: jax.Array,
    B: jax.Array,
    lam: jax.Array,
    X: jax.Array,
    which: str = "smallest",
    steps: int = 2,
    guard: int = 0,
    key: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Traceable fixed-step refinement for the vmapped batched pipelines.

    No convergence test (the step count is part of the pipeline cache
    key); otherwise identical arithmetic to ``refine_eigenpairs``.
    """
    A = A.astype(jnp.float64)
    B = B.astype(jnp.float64)
    lam = lam.astype(jnp.float64)
    X = X.astype(jnp.float64)
    if steps == 0:
        return lam, X
    s = X.shape[1]
    if key is None:
        key = jax.random.PRNGKey(1203)
    lam_q, X_q = _with_guards(lam, X, guard, which, key)

    # phases of two steps with a re-shift (and fp32 refactor) in between:
    # each pair of RR sweeps sharpens the (possibly demoted-pipeline)
    # eigenvalue estimates enough that the next factorization's shift sits
    # materially closer to the wanted end — the traceable analogue of the
    # host driver's adaptive refactor loop
    first = True
    remaining = steps
    while remaining > 0:
        phase_steps = min(2, remaining)
        remaining -= phase_steps
        anchor = lam if first else _select(lam_q, X_q, s, which)[0]
        first = False
        sigma = _sigma(anchor, which)
        lu, piv = lu_factor((A - sigma * B).astype(jnp.float32))

        def body(_, carry, lu=lu, piv=piv):
            lam_q, X_q = carry
            return _refine_step(lu, piv, A, B, lam_q, X_q)

        lam_q, X_q = jax.lax.fori_loop(0, phase_steps, body, (lam_q, X_q))
    return _select(lam_q, X_q, s, which)
