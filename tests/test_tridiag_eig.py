"""TT3/TD2 tridiagonal eigensolver: core + kernels/tridiag_eig parity.

Covers the three execution paths of ``eigh_tridiag_selected`` ('scan'
baseline, fused 'batched', Pallas 'kernel' in interpret mode), the
shuffled-``ks`` clustering regression (sort-and-restore), clustered /
graded spectra vs the LAPACK oracle, and the n=1 / s=n edges.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.tridiag_eig import (bisect_eigenvalues, eigh_tridiag_selected,
                                    inverse_iteration)
from repro.kernels.tridiag_eig.ops import (bisect_sturm, invit_batched,
                                           tridiag_eig_batched,
                                           tridiag_eig_kernel)
from repro.kernels.tridiag_eig.ref import bisect_sturm_ref, invit_ref

KEY = jax.random.PRNGKey(0)


def _rand_tridiag(n, key):
    kd, ke = jax.random.split(key)
    d = jax.random.normal(kd, (n,), jnp.float64)
    e = jax.random.normal(ke, (max(n - 1, 0),), jnp.float64)
    return d, e


def _dense(d, e):
    T = np.diag(np.asarray(d))
    if np.asarray(e).size:
        T += np.diag(np.asarray(e), 1) + np.diag(np.asarray(e), -1)
    return T


def _wilkinson(m=10):
    """W(2m+1)+: the top eigenvalue pairs agree to ~machine precision —
    the canonical cluster fixture for inverse-iteration reorthogonalization."""
    n = 2 * m + 1
    d = jnp.asarray(np.abs(np.arange(n) - m), jnp.float64)
    e = jnp.ones((n - 1,), jnp.float64)
    return d, e


def _graded(n=40):
    """Graded spectrum spanning ~12 decades (d_i = 10^{-i/3})."""
    d = jnp.asarray(10.0 ** (-np.arange(n) / 3.0), jnp.float64)
    e = 1e-4 * jnp.asarray(10.0 ** (-np.arange(n - 1) / 3.0), jnp.float64)
    return d, e


# ------------------------------------------------------------ eigenvalues --

@pytest.mark.parametrize("fixture", ["random", "clustered", "graded"])
def test_bisection_matches_eigvalsh(fixture):
    if fixture == "random":
        d, e = _rand_tridiag(48, KEY)
        ks = jnp.arange(8)
        tol = 1e-12
    elif fixture == "clustered":
        d, e = _wilkinson(10)
        ks = jnp.arange(d.shape[0] - 8, d.shape[0])
        tol = 1e-12
    else:
        d, e = _graded(40)
        ks = jnp.arange(8)
        tol = 1e-12
    ref = np.linalg.eigvalsh(_dense(d, e))
    lam = bisect_eigenvalues(d, e, ks)
    assert np.abs(np.asarray(lam) - ref[np.asarray(ks)]).max() < tol


def test_bisection_unroll_is_bitwise_neutral():
    d, e = _rand_tridiag(37, KEY)
    ks = jnp.arange(6)
    base = np.asarray(bisect_eigenvalues(d, e, ks))
    for unroll in (4, 16):
        assert np.array_equal(
            base, np.asarray(bisect_eigenvalues(d, e, ks, unroll=unroll)))


# ----------------------------------------------------------- eigenvectors --

def _check_pairs(d, e, lam, Z, rtol=1e-10):
    T = _dense(d, e)
    Z = np.asarray(Z)
    lam = np.asarray(lam)
    scale = max(np.abs(T).max(), 1.0)
    assert np.abs(T @ Z - Z * lam).max() < rtol * scale
    assert np.abs(Z.T @ Z - np.eye(Z.shape[1])).max() < rtol


def test_inverse_iteration_residual_orthogonality():
    d, e = _rand_tridiag(48, KEY)
    lam = bisect_eigenvalues(d, e, jnp.arange(8))
    Z = inverse_iteration(d, e, lam, jax.random.PRNGKey(3))
    _check_pairs(d, e, lam, Z)


def test_inverse_iteration_clustered_orthogonality():
    d, e = _wilkinson(10)
    n = d.shape[0]
    lam, Z = eigh_tridiag_selected(d, e, jnp.arange(n - 6, n))
    _check_pairs(d, e, lam, Z)


# ---------------------------------------------- shuffled-ks regression ----

def test_eigh_selected_shuffled_ks_regression():
    """Unsorted ``ks`` used to feed unsorted shifts into the gap-based
    clustering: the Wilkinson top pair landed in different clusters, MGS
    skipped them, and the returned 'eigenvectors' overlapped at ~1e-3.
    ``eigh_tridiag_selected`` must sort-and-restore."""
    d, e = _wilkinson(10)
    n = d.shape[0]
    ks = jnp.asarray([n - 1, n - 3, n - 2, n - 4])  # interleaves the pair
    lam, Z = eigh_tridiag_selected(d, e, ks)
    _check_pairs(d, e, lam, Z)
    # and the output order answers ks as given
    ref = np.linalg.eigvalsh(_dense(d, e))
    assert np.abs(np.asarray(lam) - ref[np.asarray(ks)]).max() < 1e-12


def test_eigh_selected_shuffled_matches_sorted():
    d, e = _rand_tridiag(32, jax.random.PRNGKey(7))
    ks = jnp.arange(6)
    perm = jnp.asarray([4, 0, 5, 2, 1, 3])
    lam_s, Z_s = eigh_tridiag_selected(d, e, ks)
    lam_p, Z_p = eigh_tridiag_selected(d, e, ks[perm])
    assert np.array_equal(np.asarray(lam_s)[np.asarray(perm)],
                          np.asarray(lam_p))
    assert np.array_equal(np.asarray(Z_s)[:, np.asarray(perm)],
                          np.asarray(Z_p))


# ------------------------------------------------------------------ edges --

@pytest.mark.parametrize("method", ["scan", "batched", "kernel"])
def test_n_equals_1(method):
    lam, Z = eigh_tridiag_selected(jnp.asarray([2.5]), jnp.zeros((0,)),
                                   jnp.asarray([0]), method=method)
    assert np.allclose(np.asarray(lam), [2.5])
    assert np.allclose(np.abs(np.asarray(Z)), [[1.0]])


@pytest.mark.parametrize("method", ["scan", "batched", "kernel"])
def test_s_equals_n(method):
    d, e = _rand_tridiag(12, jax.random.PRNGKey(5))
    lam, Z = eigh_tridiag_selected(d, e, jnp.arange(12), method=method)
    ref = np.linalg.eigvalsh(_dense(d, e))
    assert np.abs(np.asarray(lam) - ref).max() < 1e-12
    _check_pairs(d, e, lam, Z)


# -------------------------------------------------- batched/kernel parity --

def test_batched_path_bitwise_equals_scan():
    d, e = _rand_tridiag(45, KEY)
    ks = jnp.arange(7)
    key = jax.random.PRNGKey(11)
    lam_s, Z_s = eigh_tridiag_selected(d, e, ks, key, method="scan")
    lam_b, Z_b = eigh_tridiag_selected(d, e, ks, key, method="batched")
    assert np.array_equal(np.asarray(lam_s), np.asarray(lam_b))
    assert np.array_equal(np.asarray(Z_s), np.asarray(Z_b))


@pytest.mark.parametrize("n,s", [(33, 5), (24, 6)])
def test_bisect_kernel_interpret_bitwise_vs_ref(n, s):
    """Pallas bisection (interpret) reproduces the scan oracle BITWISE —
    same Gershgorin start, same splits, same clamped recurrence; odd n
    exercises the sublane padding."""
    if n == 24:
        d, e = _wilkinson(11)
        d, e = d[:24], e[:23]
    else:
        d, e = _rand_tridiag(n, KEY)
    ks = jnp.arange(s)
    lam_ref = bisect_sturm_ref(d, e, ks)
    lam_k = bisect_sturm(d, e, ks, force_kernel=True)
    assert np.array_equal(np.asarray(lam_ref), np.asarray(lam_k))


def test_invit_kernel_interpret_parity_random():
    d, e = _rand_tridiag(33, KEY)  # odd n: sublane padding in play
    lam = bisect_eigenvalues(d, e, jnp.arange(5))
    key = jax.random.PRNGKey(9)
    Z_ref = invit_ref(d, e, lam, key)
    Z_k = invit_batched(d, e, lam, key, force_kernel=True)
    # same start block, same algorithm; kernel reductions may reassociate
    assert np.abs(np.asarray(Z_ref) - np.asarray(Z_k)).max() < 1e-12
    _check_pairs(d, e, lam, Z_k)


def test_invit_kernel_interpret_parity_clustered():
    """Duplicate-eigenvalue clusters: the kernel's lane-masked MGS must
    orthogonalize the Wilkinson twin pairs exactly like the oracle."""
    d, e = _wilkinson(10)
    n = d.shape[0]
    lam = bisect_eigenvalues(d, e, jnp.arange(n - 6, n))
    key = jax.random.PRNGKey(9)
    Z_ref = invit_ref(d, e, lam, key)
    Z_k = invit_batched(d, e, lam, key, force_kernel=True)
    # within a machine-precision-degenerate pair, eps-level reduction
    # reassociation rotates the basis inside the invariant subspace by
    # O(sqrt(eps)) — elementwise parity is bounded accordingly, and the
    # residual/orthogonality bars below are the strict check
    assert np.abs(np.asarray(Z_ref) - np.asarray(Z_k)).max() < 2e-6
    _check_pairs(d, e, lam, Z_k)


def test_tridiag_eig_kernel_end_to_end():
    d, e = _rand_tridiag(33, jax.random.PRNGKey(21))
    ks = jnp.arange(5)
    lam, Z = tridiag_eig_kernel(d, e, ks, jax.random.PRNGKey(2))
    ref = np.linalg.eigvalsh(_dense(d, e))
    assert np.abs(np.asarray(lam) - ref[:5]).max() < 1e-12
    _check_pairs(d, e, lam, Z)


def test_default_method_autodetects_backend(monkeypatch):
    """``method=None`` resolves per backend: the compiled Pallas kernels on
    a real TPU, the fused-XLA batched program everywhere else — and the
    dispatch structure (which underlying path runs) follows the resolved
    choice, not a hard-coded default."""
    from repro.core import tridiag_eig as te

    # the resolver itself: pure function of the backend name (patching
    # jax.default_backend here runs no jax computation)
    assert te.default_tridiag_method() in ("kernel", "batched")
    monkeypatch.setattr(te.jax, "default_backend", lambda: "tpu")
    assert te.default_tridiag_method() == "kernel"
    monkeypatch.setattr(te.jax, "default_backend", lambda: "cpu")
    assert te.default_tridiag_method() == "batched"
    monkeypatch.undo()

    # dispatch structure: method=None must route through whatever the
    # resolver picked — spy on the two underlying entry points
    import repro.kernels.tridiag_eig.ops as ops
    calls = []
    real_batched, real_kernel = ops.tridiag_eig_batched, ops.tridiag_eig_kernel
    monkeypatch.setattr(ops, "tridiag_eig_batched",
                        lambda *a, **k: calls.append("batched")
                        or real_batched(*a, **k))
    # off-TPU the kernel route must still run (interpret mode)
    monkeypatch.setattr(ops, "tridiag_eig_kernel",
                        lambda *a, **k: calls.append("kernel")
                        or real_kernel(*a, force_interpret=True, **k))

    d, e = _rand_tridiag(16, jax.random.PRNGKey(3))
    monkeypatch.setattr(te, "default_tridiag_method", lambda *a: "batched")
    te.eigh_tridiag_selected(d, e, jnp.arange(3))
    assert calls == ["batched"]
    monkeypatch.setattr(te, "default_tridiag_method", lambda *a: "kernel")
    te.eigh_tridiag_selected(d, e, jnp.arange(3))
    assert calls == ["batched", "kernel"]


def test_tridiag_eig_batched_vmaps():
    """The fused path must vmap — it is what core.batched buckets run."""
    batch, n, s = 3, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(17), batch)
    ds = jax.random.normal(keys[0], (batch, n), jnp.float64)
    es = jax.random.normal(keys[1], (batch, n - 1), jnp.float64)
    ks = jnp.arange(s)
    lam, Z = jax.vmap(lambda d, e, k: tridiag_eig_batched(d, e, ks, k))(
        ds, es, keys)
    for i in range(batch):
        ref = np.linalg.eigvalsh(_dense(ds[i], es[i]))
        assert np.abs(np.asarray(lam[i]) - ref[:s]).max() < 1e-12
