"""The yardstick's copies agree with what they copy: the generator's
pencil has the spectrum it states, and the reference's residual and
B-orthogonality are the program's ``accuracy_report``."""
import jax
import numpy as np
import pytest
import scipy.linalg

from chipbench import products, reference, run

N = 300


@pytest.fixture(scope="module")
def pencil():
    """Two reflector blocks of 256 at n=300, as at the paper's n."""
    gen = run.load_module("generators", "md_like")
    return gen.build(N, jax.random.PRNGKey(2**31 + 3))


def test_generator_spectrum_is_the_stated_one(pencil):
    A, B = np.asarray(pencil.A), np.asarray(pencil.B)
    exact = np.asarray(pencil.exact_evals)
    assert np.allclose(A, A.T) and np.allclose(B, B.T)
    w = scipy.linalg.eigh(A, B, eigvals_only=True)
    assert np.max(np.abs(w - exact) / exact) < 1e-11
    assert exact[0] > 0 and np.all(np.diff(exact) > 0)


def test_tiled_product_matches_plain():
    a = jax.random.normal(jax.random.PRNGKey(0), (300, 70))
    b = jax.random.normal(jax.random.PRNGKey(1), (70, 130))
    np.testing.assert_allclose(products.matmul_tiled(a, b, tile=64),
                               np.asarray(a) @ np.asarray(b), rtol=1e-13,
                               atol=1e-12)


def test_reference_matches_accuracy_report(pencil):
    from repro.core import accuracy_report, solve
    s = 10
    res = solve(pencil.A, pencil.B, s, variant="KE", invert=True, tol=1e-9,
                krylov_block=4)
    got = reference.accuracy(pencil.A, pencil.B, res.X, res.evals,
                             pencil.exact_evals, "smallest")
    want = accuracy_report(pencil.A, pencil.B, res.X, res.evals)
    assert got["relative_residual"] == pytest.approx(
        float(want.relative_residual), rel=1e-6)
    assert got["b_orthogonality"] == pytest.approx(
        float(want.b_orthogonality), rel=1e-6)
    scale = max(np.linalg.norm(pencil.A), np.linalg.norm(pencil.B))
    err = np.max(np.abs(np.asarray(res.evals)
                        - np.asarray(pencil.exact_evals)[:s])) / scale
    assert got["eval_error"] == pytest.approx(err, rel=1e-9)
    assert max(got.values()) < 1e-12


def test_wanted_end():
    exact = np.arange(10.0)
    assert list(reference.wanted(exact, 3, "smallest")) == [0, 1, 2]
    assert list(reference.wanted(exact, 3, "largest")) == [7, 8, 9]
    with pytest.raises(ValueError):
        reference.wanted(exact, 3, "middle")
