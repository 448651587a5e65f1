"""``correct`` separates sound runs from the control and from a timed
path broken underneath, at a size a CPU test can hold.

The control is the program's own lower-precision path (the cell's
``control`` arguments: ``precision='mixed'``, no refinement). The faults
are planted in the program under the harness: a Krylov step that
returns its state unchanged, half of the wanted pairs left out (the
first half returned twice), an answer altered where it is produced, and
(on a mesh) the exchange between chips left out of the matvec.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from chipbench.tests.conftest import REPO
from chipbench.tests.drive import drive


def _unchanged_segment(op, V, T, j0, p=1, **kw):
    return V, T, jnp.zeros((p, p), V.dtype)


def _finalize_fault(fault):
    from repro.core import gsyeig
    finalize = gsyeig._finalize

    def broken(lam, X, *args, **kw):
        res = finalize(lam, X, *args, **kw)
        h = res.evals.shape[0] // 2
        if fault == "half":
            res.evals = res.evals.at[h:2 * h].set(res.evals[:h])
            res.X = res.X.at[:, h:2 * h].set(res.X[:, :h])
        else:
            res.evals = res.evals.at[0].multiply(1.0 + 1e-6)
        return res
    return broken


def test_sound_runs_are_correct(tiny_root):
    root, bench = tiny_root
    for seed in (11, 2**31 + 7):
        res = drive(root, bench, "tiny-ke", seed=seed)
        assert res["correct"], res
        assert res["attempted"] >= 1 and res["failed"] == 0
        assert set(res["metrics"]) == {"solve_s", "peak_hbm_gb", "setup_s"}


def test_control_is_refused(tiny_root):
    root, bench = tiny_root
    res = drive(root, bench, "tiny-ke", control=True)
    assert not res["correct"], res
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("fault", ["unchanged_step", "half", "altered"])
def test_broken_timed_path_is_refused(tiny_root, monkeypatch, fault):
    from repro.core import gsyeig, lanczos
    if fault == "unchanged_step":
        monkeypatch.setattr(lanczos, "_lanczos_segment", _unchanged_segment)
    else:
        monkeypatch.setattr(gsyeig, "_finalize", _finalize_fault(fault))
    root, bench = tiny_root
    res = drive(root, bench, "tiny-ke")
    assert not res["correct"], res
    assert res["failed"] >= 1


# the mesh cell, on four host devices in a process of its own (the device
# count is fixed when JAX starts)
_MESH = textwrap.dedent("""
    import json, os, sys
    from pathlib import Path
    import jax
    jax.config.update("jax_enable_x64", True)
    from chipbench.tests.conftest import tiny_cell
    from chipbench.tests.drive import drive
    from chipbench.tests import test_correct as tc
    from repro.core import gsyeig
    from repro.dist import eigensolver

    root = Path(sys.argv[1])
    bench = tiny_cell(root, "tiny-ke.2x2", "md-ke.2x2")
    runs = {"sound": lambda: None}

    def no_exchange(c_blk, ncm, ax):
        def matvec(X):
            mi = jax.lax.axis_index("model")
            Xs = jax.lax.dynamic_slice_in_dim(X, mi * ncm, ncm, axis=0)
            W = c_blk @ Xs                   # the psum over 'model' left out
            if ax is not None:
                W = jax.lax.all_gather(W, ax, axis=0, tiled=True)
            return W
        return matvec

    def unchanged(matvec, V, T, j0, p=1):
        return V, T, jax.numpy.zeros((p, p), V.dtype)

    finalize = gsyeig._finalize
    out = {}
    for name in ("sound", "control", "no_exchange", "unchanged_step",
                 "half", "altered"):
        eigensolver.ke_restart_program.cache_clear()
        eigensolver._fused_block_matvec = (
            no_exchange if name == "no_exchange" else tc.ORIGINAL_MATVEC)
        eigensolver._segment_impl = (
            unchanged if name == "unchanged_step" else tc.ORIGINAL_SEGMENT)
        gsyeig._finalize = (tc._finalize_fault(name)
                            if name in ("half", "altered") else finalize)
        out[name] = drive(root, bench, "tiny-ke.2x2", chips=4,
                          control=name == "control")
    print("MESH " + json.dumps(out))
""")


def _originals():
    from repro.dist import eigensolver
    return eigensolver._fused_block_matvec, eigensolver._segment_impl


ORIGINAL_MATVEC, ORIGINAL_SEGMENT = _originals()


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "benchmarks"), str(REPO / "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", _MESH, str(tmp_path_factory.mktemp("mesh"))],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("MESH ")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    return json.loads(lines[-1][5:])


def test_mesh_sound_run_is_correct(mesh_runs):
    res = mesh_runs["sound"]
    assert res["correct"], res
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["control", "no_exchange",
                                   "unchanged_step", "half", "altered"])
def test_mesh_broken_path_is_refused(mesh_runs, fault):
    res = mesh_runs[fault]
    assert not res["correct"], res
    assert res["failed"] >= 1
