"""The TPU-path stage programs of ``core/looped.py``, run on the CPU.

On a TPU the dense f64 stages run as flat-compile loops (and the small
decompositions XLA cannot compile inside a multi-device program, or does
not compute to f64 accuracy, run without XLA's decomposition ops). The
choice is made from ``kernels.dispatch.on_tpu``; the tests below patch it
to walk those paths here and compare them with LAPACK at f64 tolerances.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import accuracy_report, solve
from repro.core import looped
from repro.core.cholesky import cholesky_blocked, cholesky_upper
from repro.data.problems import md_like
from repro.kernels import dispatch


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)


def _spd(n, seed):
    M = jax.random.normal(jax.random.PRNGKey(seed), (n, n), jnp.float64)
    return M @ M.T / n + jnp.eye(n)


@pytest.mark.parametrize("n,block", [(70, 16), (300, 256), (513, 256)])
def test_cholesky_blocked_matches_lapack(on_tpu, n, block):
    """Identity-padded to a block multiple, unblocked diagonal tiles."""
    B = _spd(n, n)
    U = jax.jit(cholesky_blocked, static_argnames="block")(B, block=block)
    Ur = np.linalg.cholesky(np.asarray(B)).T
    np.testing.assert_allclose(np.asarray(U), Ur, rtol=0, atol=1e-13)
    assert looped.looped(n) == (n > looped.LOOP_BLOCK)


@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("n,k", [(300, 5), (513, 513), (513, 1)])
def test_solve_upper_looped_matches_lapack(on_tpu, trans, n, k):
    U = jnp.asarray(np.linalg.cholesky(np.asarray(_spd(n, 1))).T)
    Y = jax.random.normal(jax.random.PRNGKey(2), (n, k), jnp.float64)
    X = looped.solve_upper_looped(U, Y, trans, block=128)
    Ut = np.asarray(U).T if trans else np.asarray(U)
    np.testing.assert_allclose(Ut @ np.asarray(X), np.asarray(Y), atol=1e-12)
    x = looped.solve_upper_looped(U, Y[:, 0], trans, block=128)
    np.testing.assert_allclose(np.asarray(x), np.asarray(X[:, 0]), atol=1e-13)


def test_matmul_tiled_trailing_window():
    a = jax.random.normal(jax.random.PRNGKey(0), (300, 70), jnp.float64)
    b = jax.random.normal(jax.random.PRNGKey(1), (70, 500), jnp.float64)
    c = jax.random.normal(jax.random.PRNGKey(2), (300, 500), jnp.float64)
    ref = np.asarray(c + a @ b)
    got = np.asarray(looped.matmul_tiled(a, b, c, tile=128))
    np.testing.assert_allclose(got, ref, atol=1e-12)
    got = np.asarray(jax.jit(lambda s: looped.matmul_tiled(
        a, b, c, start=s, tile=128))(200))
    # tiles reaching past row and column 200 are updated, the rest kept
    np.testing.assert_allclose(got[128:, 128:], ref[128:, 128:], atol=1e-12)
    np.testing.assert_array_equal(got[:128], np.asarray(c)[:128])
    np.testing.assert_array_equal(got[:, :128], np.asarray(c)[:, :128])


def test_small_decompositions_on_emulated_f64(on_tpu):
    """Unblocked Cholesky, the Householder block QR, CholeskyQR2 and the
    host eigh agree with LAPACK."""
    X = jax.random.normal(jax.random.PRNGKey(3), (400, 7), jnp.float64)
    G = X.T @ X
    assert looped.emulated_f64(jnp.float64)
    assert not looped.emulated_f64(jnp.float32)
    np.testing.assert_allclose(np.asarray(looped.cholesky_tile(G)),
                               np.linalg.cholesky(np.asarray(G)).T, atol=1e-12)
    Q, R = jax.jit(looped.qr_posdiag)(X)
    np.testing.assert_allclose(np.asarray(Q.T @ Q), np.eye(7), atol=1e-14)
    np.testing.assert_allclose(np.asarray(Q @ R), np.asarray(X), atol=1e-13)
    assert (np.diagonal(np.asarray(R)) > 0).all()
    np.testing.assert_allclose(np.abs(np.asarray(looped.orthonormalize(X))),
                               np.abs(np.asarray(Q)), atol=1e-13)
    w, V = jax.jit(looped.eigh_small)(G)
    np.testing.assert_allclose(np.asarray(w),
                               np.linalg.eigvalsh(np.asarray(G)), rtol=1e-13)
    np.testing.assert_allclose(np.asarray(V.T @ G @ V), np.diag(np.asarray(w)),
                               atol=1e-11)


def test_ke_solve_on_the_tpu_path_meets_table3(on_tpu):
    """The whole local KE solve through the looped stages, the Householder
    block QR and the host eigh — the chip smoke's path at a CPU-sized n —
    on a pencil from the reflector-product generator, against its exact
    spectrum."""
    n, s = 600, 8
    prob = md_like(n, key=jax.random.PRNGKey(4))
    assert cholesky_upper(prob.B).shape == (n, n)
    res = solve(prob.A, prob.B, s, variant="KE", invert=True, tol=1e-9,
                krylov_block=4)
    acc = accuracy_report(prob.A, prob.B, res.X, res.evals)
    exact = np.asarray(prob.exact_evals)[:s]
    assert np.max(np.abs(np.asarray(res.evals) - exact)) < 1e-12 * exact.max()
    assert float(acc.relative_residual) < 1e-12
    assert float(acc.b_orthogonality) < 1e-12
    assert res.info["converged"] and not res.info["recovery"]
