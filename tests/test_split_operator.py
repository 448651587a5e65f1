"""KE's operator in its int8 split form (``core.operators.SplitC``).

On a TPU, f64 is emulated and an f64 product re-splits C into pieces at
every block step. KE there holds C as exact int8 slices, formed once a
solve inside GS2's program, and each step's product reads only those.
The tests run the split on the CPU, with ``dispatch.on_tpu`` patched as in
``tests/test_looped.py`` where the choice depends on it.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accuracy_report, instrument, looped, solve
from repro.core.operators import apply_op, split_c
from repro.data.problems import md_like
from repro.kernels import dispatch
from repro.resilience import SolverError
from repro.resilience.faults import NanPoison, inject

EPS = np.finfo(np.float64).eps
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)


def _hard_c(n, seed):
    """Rows whose scales span 1e-30 to 1e30, an all-zero row and a row
    holding one entry."""
    rng = np.random.default_rng(seed)
    C = rng.standard_normal((n, n)) * 10.0 ** rng.uniform(-30, 30, (n, 1))
    C[0] = 10.0 ** -30
    C[1] = 10.0 ** 30
    C[3] = 0.0
    C[5] = 0.0
    C[5, n // 2] = -3.25e-7
    return C, rng


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("n", [300, 1100, 2049])
def test_split_product_matches_f64(n, p):
    """Within 4 eps |C||V| of NumPy's f64 product, entry by entry; a
    vector (p = 1) and an (n, p) block."""
    C, rng = _hard_c(n, n + p)
    V = rng.standard_normal((n,) if p == 1 else (n, p))
    op = jax.jit(split_c)(jnp.asarray(C))
    y = np.asarray(jax.jit(apply_op)(op, jnp.asarray(V)))
    bound = 4 * EPS * (np.abs(C) @ np.abs(V))
    assert np.all(np.abs(y - C @ V) <= bound)
    assert not y[3].any()


@pytest.mark.parametrize("n", [300, 2049])
def test_split_form_takes_the_bytes_of_c(n):
    """8 int8 slices an entry and one scale a row: 8n^2 + 8n bytes, as C
    takes 8n^2 in f64."""
    op = jax.eval_shape(split_c, jax.ShapeDtypeStruct((n, n), jnp.float64))
    assert op.Q.dtype == jnp.int8 and op.Q.shape == (n, 8, n)
    assert sum(a.size * a.dtype.itemsize for a in op) <= 8 * n * n + 8 * n


@pytest.mark.parametrize("n,tile", [(300, 256), (1100, looped.MM_TILE)])
def test_ke_solve_on_the_split_operator_meets_table3(on_tpu, monkeypatch,
                                                      n, tile):
    """A KE solve through ``solve`` on the TPU path runs on the split form
    above one product tile (n = 300 with the tile cut to 256) and meets
    the Table-3 bars against the pencil's exact spectrum."""
    monkeypatch.setattr(looped, "MM_TILE", tile)
    s = 8
    prob = md_like(n, key=jax.random.PRNGKey(4))
    res = solve(prob.A, prob.B, s, variant="KE", invert=True, tol=1e-9,
                krylov_block=4)
    assert res.info["counters"]["split_operator"] == 1
    acc = accuracy_report(prob.A, prob.B, res.X, res.evals)
    exact = np.asarray(prob.exact_evals)[:s]
    assert np.max(np.abs(np.asarray(res.evals) - exact)) < 1e-12 * exact.max()
    assert float(acc.relative_residual) < 1e-12
    assert float(acc.b_orthogonality) < 1e-12
    assert res.info["converged"] and not res.info["recovery"]


@pytest.mark.parametrize("n,kwargs", [
    (1100, dict(precision="mixed", refine=False)),
    (300, {}),
], ids=["mixed", "one-tile"])
def test_split_operator_stays_off(on_tpu, n, kwargs):
    """A demoted operator (the benchmark's control) and a C of one tile
    keep the f64 ``ExplicitC``."""
    prob = md_like(n, key=jax.random.PRNGKey(4))
    res = solve(prob.A, prob.B, 4, variant="KE", invert=True, tol=1e-9,
                krylov_block=4, **kwargs)
    assert res.info["counters"]["split_operator"] == 0


def test_split_operator_stays_off_on_the_mesh():
    """The two-device mesh path, on the TPU path at n = 1100, keeps its
    own fused matvec."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        jax.config.update("jax_enable_x64", True)
        from repro.kernels import dispatch
        dispatch.on_tpu = lambda: True
        from repro.core import solve
        from repro.data.problems import md_like
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((2, 1), ("data", "model"))
        prob = md_like(1100, key=jax.random.PRNGKey(4))
        res = solve(prob.A, prob.B, 4, variant="KE", invert=True, tol=1e-9,
                    krylov_block=4, mesh=mesh)
        assert res.info["converged"], res.info
        print("SPLIT_OPERATOR", res.info["counters"]["split_operator"])
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH="src"),
                         cwd=_ROOT)
    assert "SPLIT_OPERATOR 0" in out.stdout, out.stdout + out.stderr[-3000:]


@pytest.mark.chaos
@pytest.mark.parametrize("stage", ["GS2", "KE_iter"])
def test_poison_on_the_split_path_is_diagnosed(on_tpu, stage):
    """A NaN in A before GS2 fails GS2's verdict, taken on C before the
    split; a NaN poisoned into the split operator's scales at the
    ``KE_iter`` seam reaches the restart's non-finite verdict."""
    prob = md_like(1100, key=jax.random.PRNGKey(4))
    with instrument.counting() as counters, inject(NanPoison(stage)):
        with pytest.raises(SolverError) as exc:
            solve(prob.A, prob.B, 4, variant="KE", invert=True, tol=1e-9,
                  krylov_block=4)
    d = exc.value.diagnosis
    assert d["reason"] == "nonfinite_stage" and d["stage"] == stage
    assert d["health"]["first_unhealthy_stage"] == stage
    assert counters["split_operator"] == (stage == "KE_iter")

