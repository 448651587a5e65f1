"""Dense stage programs whose compile time does not grow with n.

XLA's TPU backend expands ``cholesky`` and ``triangular_solve`` into
unrolled block sweeps, and in f64 (emulated on a v5e) every unrolled step
is large: compiling GS1 for a described v5e took ~54 s at n=2,048 and did
not finish in 400 s at n=9,997. The emulation also keeps ~16x the output
(and ~6.5x the larger input) of an f64 matmul as temporaries, so one
(n, n) f64 product at n=10,240 asks for ~13 GB of a 16 GB chip.

The programs here avoid both. ``solve_upper_looped`` (and
``core.cholesky.cholesky_blocked``) run ONE ``lax.fori_loop`` over fixed
``(LOOP_BLOCK, LOOP_BLOCK)`` diagonal tiles — the loop body is the same
program at every n, so the compile is flat — and ``matmul_tiled`` forms a
large f64 product one ``(MM_TILE, MM_TILE)`` output tile per loop step, so
the emulation's temporaries are sized by the tile, not by n.

The small decompositions of the emulated-f64 path (``emulated_f64``)
avoid XLA's f64 Cholesky, QR and ``eigh`` ops, which the TPU compiler
cannot build inside a multi-device program or (``eigh``) does not compute
to f64 accuracy.

What runs is picked per call from the platform, the dtype and the shape:
``looped(n)`` selects the loops on a TPU above one tile, and ``matmul``
tiles only f64 products there. Everywhere else (and on a CPU host, where
these are LAPACK/Eigen calls) XLA's own ops run unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.linalg import solve_triangular

from repro.kernels import dispatch

from .instrument import span

#: tile edge of the looped factorizations and solves
LOOP_BLOCK = 256
#: output tile edge of ``matmul_tiled``
MM_TILE = 1024


def looped(n: int) -> bool:
    """True when an (n, n) factorization or solve should run as the
    flat-compile ``fori_loop`` program."""
    return dispatch.on_tpu() and n > LOOP_BLOCK


def emulated_f64(dtype) -> bool:
    """f64 on a TPU: XLA emulates it, and cannot compile its f64 Cholesky
    or QR decompositions inside a multi-device program (the TPU compiler
    rejects them: "A tuple parameter that is being flattened shouldn't
    have frontend attributes"), and its f64 ``eigh`` is not f64-accurate.
    ``cholesky_tile``, ``qr_posdiag``, ``orthonormalize`` and
    ``eigh_small`` then avoid those ops."""
    return dispatch.on_tpu() and jnp.dtype(dtype) == jnp.float64


def cholesky_tile(A: jax.Array) -> jax.Array:
    """Upper U with A = U^T U for a small (tile-sized) SPD A."""
    if emulated_f64(A.dtype):
        return cholesky_unblocked(A)
    return jnp.linalg.cholesky(A).T


def cholesky_unblocked(A: jax.Array) -> jax.Array:
    """Outer-product Cholesky, one row of U per ``fori_loop`` step — no
    decomposition op at all. A non-SPD A yields NaN rows, as XLA's does."""
    n = A.shape[0]
    cols = jnp.arange(n)

    def step(j, carry):
        U, M = carry
        row = lax.dynamic_index_in_dim(M, j, keepdims=False)
        row = jnp.where(cols >= j, row / jnp.sqrt(row[j]), 0)
        return U.at[j].set(row), M - jnp.outer(row, row)

    return lax.fori_loop(0, n, step, (jnp.zeros_like(A), A))[0]


def qr_posdiag(X: jax.Array):
    """Reduced QR of a tall, narrow X with diag(R) >= 0 — unique for
    full-rank X, and for one column exactly x/||x||, ||x||. Householder
    either way: XLA's where it compiles, on emulated f64 the unrolled
    reflectors of ``linalg_utils.qr_wy`` (the Lanczos residual block can
    be nearly rank-deficient, which a Cholesky-based QR would not
    survive)."""
    if emulated_f64(X.dtype):
        from .linalg_utils import qr_wy
        n, p = X.shape
        V, T, R = qr_wy(X)
        Q = jnp.eye(n, p, dtype=X.dtype) - V @ (T @ V[:p].T)
        R = R[:p]
    else:
        Q, R = jnp.linalg.qr(X)
    sgn = jnp.sign(jnp.diagonal(R))
    sgn = jnp.where(sgn == 0, jnp.ones_like(sgn), sgn)
    return Q * sgn[None, :], R * sgn[:, None]


def eigh_small(T: jax.Array):
    """(w ascending, V) of a small symmetric T — the projected problems of
    the Krylov restarts. XLA's f64 ``eigh`` on a v5e is not f64-accurate
    (3e-11 relative eigenvalue error on a well-conditioned 256x256 matrix,
    where f64 LAPACK gives ~1e-15), which misses the Table-3 bars; on
    emulated f64 the host's LAPACK solves it through a callback (one
    (m, m) transfer per restart), in the span ``repro.ke.host_eigh`` on
    whichever host thread runs the callback."""
    if emulated_f64(T.dtype):
        # one (m+1, m) result — w stacked on V: a callback with two results
        # does not lower inside a multi-device TPU program
        def host_eigh(t):
            with span("ke.host_eigh"):
                w, v = np.linalg.eigh(t)
                wv = np.concatenate([w[..., None, :], v], axis=-2)
                return wv.astype(t.dtype)

        shape = T.shape[:-2] + (T.shape[-2] + 1, T.shape[-1])
        wv = jax.pure_callback(host_eigh, jax.ShapeDtypeStruct(shape, T.dtype),
                               T, vmap_method="expand_dims")
        return wv[..., 0, :], wv[..., 1:, :]
    return jnp.linalg.eigh(T)


def cholesky_qr(X: jax.Array) -> jax.Array:
    """One CholeskyQR pass: X R^{-1} with R = chol(X^T X)."""
    from .cholesky import cholesky_upper
    R = cholesky_upper(matmul(X.T, X))
    return solve_upper(R, X.T, trans=True).T


def orthonormalize(X: jax.Array) -> jax.Array:
    """An orthonormal basis of the columns of a well-conditioned X (Ritz
    vectors of an orthonormal basis): XLA's QR, or CholeskyQR2 on
    emulated f64."""
    if emulated_f64(X.dtype):
        return cholesky_qr(cholesky_qr(X))
    return jnp.linalg.qr(X)[0]


def pad_identity(M: jax.Array, N: int) -> jax.Array:
    """Embed (n, n) M in the leading corner of an (N, N) identity — the
    padding that leaves a Cholesky factor or a triangular solve of the
    leading block unchanged."""
    n = M.shape[0]
    if N == n:
        return M
    idx = jnp.arange(n, N)
    return (jnp.zeros((N, N), M.dtype).at[:n, :n].set(M)
            .at[idx, idx].set(1))


def _pad2(M: jax.Array, r: int, c: int) -> jax.Array:
    if (r, c) == M.shape:
        return M
    return jnp.pad(M, ((0, r - M.shape[0]), (0, c - M.shape[1])))


def matmul_tiled(a: jax.Array, b: jax.Array, c: jax.Array | None = None,
                 start: int | jax.Array = 0, tile: int = MM_TILE
                 ) -> jax.Array:
    """``c + a @ b`` (``a @ b`` when c is None) one output tile per step
    of a single ``fori_loop``.

    Only the tiles that reach past row AND column ``start`` are computed
    (the rest of the result is c, or zero) — the trailing window of a
    blocked factorization, with a traced ``start``."""
    m, k = a.shape
    n = b.shape[1]
    tm, tn = min(tile, m), min(tile, n)
    nm, nn = -(-m // tm), -(-n // tn)
    a_p = _pad2(a, nm * tm, k)
    b_p = _pad2(b, k, nn * tn)
    out = (jnp.zeros((nm * tm, nn * tn), jnp.result_type(a, b))
           if c is None else _pad2(c, nm * tm, nn * tn))
    # first live tile row / column, and the live tile grid's width
    i0 = jnp.minimum(start // tm, nm)
    j0 = jnp.minimum(start // tn, nn)
    wj = nn - j0

    def step(t, out):
        r0 = (i0 + t // wj) * tm
        c0 = (j0 + t % wj) * tn
        ai = lax.dynamic_slice(a_p, (r0, 0), (tm, k))
        bj = lax.dynamic_slice(b_p, (0, c0), (k, tn))
        cij = lax.dynamic_slice(out, (r0, c0), (tm, tn))
        return lax.dynamic_update_slice(out, cij + ai @ bj, (r0, c0))

    return lax.fori_loop(0, (nm - i0) * wj, step, out)[:m, :n]


# shared by every eager caller (e.g. the pencil generator), so products of
# one shape compile once
_matmul_tiled = jax.jit(matmul_tiled, static_argnames=("tile",))


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b``, tiled (``matmul_tiled``) when an f64 operand or result
    spans more than one tile on a TPU; b may be a vector."""
    if b.ndim == 1:
        return matmul(a, b[:, None])[:, 0]
    if (max(a.shape[0], b.shape[1]) > MM_TILE and dispatch.on_tpu()
            and jnp.result_type(a, b) == jnp.float64):
        return _matmul_tiled(a, b)
    return a @ b


def solve_upper(U: jax.Array, Y: jax.Array, trans: bool = False
                ) -> jax.Array:
    """X = U^{-1} Y (``trans=False``) or X = U^{-T} Y (``trans=True``) for
    upper-triangular U; Y may be (n,) or (n, k)."""
    if looped(U.shape[0]):
        return solve_upper_looped(U, Y, trans)
    return solve_triangular(U, Y, trans=int(trans), lower=False)


def solve_upper_looped(U: jax.Array, Y: jax.Array, trans: bool = False,
                       block: int = LOOP_BLOCK) -> jax.Array:
    """Block substitution as one ``fori_loop`` over (block, block) tiles.

    Step k solves the diagonal tile against its row block of Y minus the
    coupling to the rows already solved. The coupling is the full-width
    (block, N) row block of U (``trans=False``, backward) or of U^T
    (``trans=True``, forward) against the whole of X, whose unsolved rows
    are still zero — so every step has the same shapes."""
    vec = Y.ndim == 1
    Y2 = Y[:, None] if vec else Y
    n, k = Y2.shape
    b = min(block, n)
    nb = -(-n // b)
    N = nb * b
    Up = pad_identity(U, N)
    Yp = _pad2(Y2, N, k)
    mm = matmul_tiled if Y2.dtype == jnp.float64 else jnp.matmul

    def step(i, X):
        kb = i if trans else nb - 1 - i
        k0 = kb * b
        if trans:
            coup = lax.dynamic_slice(Up, (0, k0), (N, b)).T
        else:
            coup = lax.dynamic_slice(Up, (k0, 0), (b, N))
        rhs = lax.dynamic_slice(Yp, (k0, 0), (b, k)) - mm(coup, X)
        Ukk = lax.dynamic_slice(Up, (k0, k0), (b, b))
        Xk = solve_triangular(Ukk, rhs, trans=int(trans), lower=False)
        return lax.dynamic_update_slice(X, Xk, (k0, 0))

    X = lax.fori_loop(0, nb, step, jnp.zeros((N, k), Y2.dtype))[:n]
    return X[:, 0] if vec else X


__all__ = ["LOOP_BLOCK", "MM_TILE", "looped", "emulated_f64",
           "cholesky_tile", "cholesky_unblocked", "eigh_small", "qr_posdiag",
           "orthonormalize", "pad_identity",
           "matmul_tiled", "matmul", "solve_upper", "solve_upper_looped"]
