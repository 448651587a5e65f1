"""Distribution layer: checkpoint round-trip/atomicity, error-feedback
compression, straggler monitor, elastic remesh plans, partitioning rules."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.dist import checkpoint as ckpt
from repro.dist.compression import (compress_with_feedback, decompress,
                                    init_ef_state)
from repro.dist.elastic import plan_remesh
from repro.dist.straggler import StragglerMonitor


# ------------------------------------------------------------ checkpoint --

def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"a": jax.random.normal(k1, (4, 8)),
            "nested": {"b": jax.random.normal(k2, (3,)),
                       "step": jnp.asarray(7)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), 12, t, extra={"cursor": 34})
    out = ckpt.load_latest(str(tmp_path), t)
    assert out is not None
    step, restored, extra = out
    assert step == 12 and extra["cursor"] == 34
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)),
                 t, restored)


def test_checkpoint_retention_and_latest(tmp_path):
    t = _tree(jax.random.PRNGKey(1))
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(str(tmp_path), s, t, keep=3)
    assert ckpt.latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert len([d for d in kept if d.startswith("step_")]) == 3


def test_checkpoint_skips_corrupt(tmp_path):
    t = _tree(jax.random.PRNGKey(2))
    ckpt.save(str(tmp_path), 1, t)
    # simulate a crash mid-write: tmp dir without manifest
    os.makedirs(tmp_path / "step_00000002.tmp")
    # and a finalized-looking dir without manifest
    os.makedirs(tmp_path / "step_00000003")
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_lanczos_checkpoint_resume(tmp_path):
    """A preempted eigensolve resumes from the persisted factorization."""
    from repro.core import ExplicitC, lanczos_solve
    n, s = 64, 4
    key = jax.random.PRNGKey(3)
    lam = jnp.sort(jax.random.normal(key, (n,), jnp.float64)) * 5
    Q, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, 1),
                                           (n, n), jnp.float64))
    C = 0.5 * ((Q * lam[None, :]) @ Q.T + ((Q * lam[None, :]) @ Q.T).T)
    cb = ckpt.lanczos_callback(str(tmp_path), every=1)
    res = lanczos_solve(ExplicitC(C), s, which="SA", callback=cb)
    assert res.converged
    saved = ckpt.load_latest(str(tmp_path),
                             {"V": jnp.zeros((n, 21)),
                              "T": jnp.zeros((21, 21))})
    assert saved is not None
    _, fact, extra = saved
    assert extra["kind"] == "lanczos"
    assert fact["V"].shape[0] == n


# ----------------------------------------------------------- compression --

def test_ef_compression_bounded_error():
    key = jax.random.PRNGKey(4)
    g = {"w": jax.random.normal(key, (64, 64), jnp.float32)}
    ef = init_ef_state(g)
    q, s, ef = compress_with_feedback(g, ef)
    deq = decompress(q, s)
    # int8 quantization error <= scale/2 per element + EF carries the rest
    err = np.abs(np.asarray(deq["w"]) - np.asarray(g["w"])).max()
    assert err <= float(s["w"]) * 0.5 + 1e-6
    assert q["w"].dtype == jnp.int8


def test_ef_accumulates_small_signals():
    """EF telescopes: sum of transmissions = sum of gradients - final error,
    so even signals far below one quantization step get through eventually."""
    g = {"w": jnp.full((8, 8), 1e-4, jnp.float32)
         .at[0, 0].set(1.0)}  # scale ~ 1/127 >> 1e-4
    ef = init_ef_state(g)
    total = jnp.zeros((8, 8), jnp.float32)
    last_scale = 0.0
    for _ in range(100):
        q, s, ef = compress_with_feedback(g, ef)
        total = total + decompress(q, s)["w"]
        last_scale = float(s["w"])
    # telescoping: |total - 100 g| = |e_final| <= one quantization step
    err = float(jnp.abs(total[1, 1] - 100 * 1e-4))
    assert err <= last_scale, (err, last_scale)
    # and without EF nothing would ever be transmitted for this element
    q0, s0 = jnp.round(g["w"][1, 1] / last_scale), last_scale
    assert float(q0) == 0.0


# -------------------------------------------------------------- straggler --

def test_straggler_detection_and_rebalance():
    mon = StragglerMonitor(n_hosts=8)
    for step in range(5):
        for h in range(8):
            mon.record(h, 1.0 if h != 3 else 2.5)  # host 3 is slow
    assert mon.stragglers() == [3]
    plan = mon.rebalance_plan(microbatches_per_host=4)
    assert sum(plan.values()) == 32
    assert plan[3] < 4           # slow host sheds load
    assert max(plan.values()) <= 6


def test_straggler_none_when_uniform():
    mon = StragglerMonitor(n_hosts=4)
    for _ in range(4):
        for h in range(4):
            mon.record(h, 1.0)
    assert mon.stragglers() == []
    plan = mon.rebalance_plan(2)
    assert all(v == 2 for v in plan.values())


# ---------------------------------------------------------------- elastic --

def test_plan_remesh_keeps_tp():
    p = plan_remesh(512, model_parallel=16, pods=2)
    assert p.new_shape == (2, 16, 16)
    p2 = plan_remesh(480, model_parallel=16)  # lost 32 chips
    assert p2.new_shape == (30, 16)
    p3 = plan_remesh(500, model_parallel=16)  # ragged: drop remainder
    assert p3.new_shape == (31, 16)
    assert "dropping" in p3.note


def test_plan_remesh_rejects_impossible():
    with pytest.raises(ValueError):
        plan_remesh(8, model_parallel=16)


# ---------------------------------------------------------- partitioning --

@pytest.mark.slow
def test_partitioning_rules_shape_aware():
    """Run in a subprocess with 8 host devices to exercise a real mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from repro.configs import smoke_config
        from repro.dist.partitioning import (param_shardings,
                                             decode_state_shardings,
                                             batch_shardings)
        from repro.models.model import init_params, init_decode_state
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("qwen2-moe-a2.7b")
        shapes = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                                jax.random.PRNGKey(0))
        sh = param_shardings(mesh, shapes)
        flat = jax.tree_util.tree_leaves_with_path(sh)
        specs = {"/".join(str(k) for k in p): s.spec for p, s in flat}
        # experts sharded over model (EP)
        ep = [v for k, v in specs.items() if "w_gate" in k]
        assert any("model" in str(s) for s in ep), ep
        st = jax.eval_shape(lambda: init_decode_state(cfg, 8, capacity=32))
        dsh = decode_state_shardings(mesh, st)
        bsh = batch_shardings(mesh, {
            "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)})
        assert "data" in str(bsh["tokens"].spec)
        # B=1 batch must NOT get sharded over data
        bsh1 = batch_shardings(mesh, {
            "tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)})
        assert bsh1["tokens"].spec == P(None, None)
        print("PARTITION_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "PARTITION_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_sharded_la_multidevice():
    """Distributed symv/gemm/cholesky/trsm on an 8-device subprocess mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.dist.sharded_la import (dist_symv, dist_gemm, dist_gemm_rs,
                                           dist_cholesky, dist_trsm_left_t)
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        n = 64
        key = jax.random.PRNGKey(0)
        M = jax.random.normal(key, (n, n), jnp.float64)
        A = 0.5 * (M + M.T)
        x = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float64)
        y = dist_symv(mesh, A, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(A @ x),
                                   rtol=1e-12)
        Bm = jax.random.normal(jax.random.fold_in(key, 2), (n, 16),
                               jnp.float64)
        np.testing.assert_allclose(np.asarray(dist_gemm(mesh, A, Bm)),
                                   np.asarray(A @ Bm), rtol=1e-11, atol=1e-11)
        np.testing.assert_allclose(np.asarray(dist_gemm_rs(mesh, A, Bm)),
                                   np.asarray(A @ Bm), rtol=1e-11, atol=1e-11)
        SPD = A @ A.T + n * jnp.eye(n)
        U = dist_cholesky(mesh, SPD)
        np.testing.assert_allclose(np.asarray(U.T @ U), np.asarray(SPD),
                                   rtol=1e-10, atol=1e-8)
        W = dist_trsm_left_t(mesh, U, Bm)
        np.testing.assert_allclose(np.asarray(U.T @ W), np.asarray(Bm),
                                   rtol=1e-10, atol=1e-8)
        print("SHARDED_LA_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "SHARDED_LA_OK" in out.stdout, out.stdout + out.stderr[-3000:]


_TT_PARITY_TEMPLATE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.data.problems import md_like
    from repro.core import solve
    from repro.dist.eigensolver import solve_tt_distributed
    from repro.dist.partitioning import make_mesh
    mesh = make_mesh({mesh_shape}, ("data", "model"))
    prob = md_like({n})
    ref = solve(prob.A, prob.B, {s}, variant="TT", band_width={w})
    evals, X = solve_tt_distributed(mesh, prob.A, prob.B, {s},
                                    band_width={w})
    np.testing.assert_allclose(np.asarray(evals), np.asarray(ref.evals),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(evals),
                               np.asarray(prob.exact_evals[:{s}]),
                               rtol=1e-7, atol=1e-9)
    R = np.asarray(prob.A @ X - (prob.B @ X) * np.asarray(evals)[None, :])
    rel = np.linalg.norm(R) / np.linalg.norm(np.asarray(prob.A))
    assert rel < 1e-10, rel
    # the auto router must dispatch onto a distributed variant and agree
    res_auto = solve(prob.A, prob.B, {s}, variant="auto", mesh=mesh,
                     band_width={w})
    assert res_auto.info["variant"] in ("TT", "KE"), res_auto.info
    assert res_auto.info["router"]["n_devices"] == {ndev}
    np.testing.assert_allclose(np.asarray(res_auto.evals),
                               np.asarray(prob.exact_evals[:{s}]),
                               rtol=1e-6, atol=1e-8)
    print("DIST_TT_OK")
"""


def _run_tt_parity(ndev, mesh_shape, n, s, w):
    code = textwrap.dedent(_TT_PARITY_TEMPLATE.format(
        ndev=ndev, mesh_shape=mesh_shape, n=n, s=s, w=w))
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_TT_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_distributed_tt1_fused_sweep_two_device():
    """Fast lane: the fused one-program ``dist_reduce_to_band`` on a
    2-device (2, 1) mesh — data=2, so the row collectives are real —
    (a) is numerically at parity with the local
    ``reduce_to_band`` band, (b) satisfies the reduction invariants, and
    (c) issues O(1) host dispatches per sweep (the registry's
    ``TT1_FUSED_MAX_DISPATCHES``) — while the stepwise per-panel baseline
    pays O(n/w), proving the counter counts."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.analysis.static_audit import (
            TT1_FUSED_MAX_DISPATCHES, TT1_STEPWISE_DISPATCHES_PER_PANEL)
        from repro.core.band_storage import unpack_band
        from repro.core.sbr import reduce_to_band
        from repro.dist import eigensolver as de
        # data=2: the row collectives (all_gather/psum) are real, not no-ops
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((2, 1), ("data", "model"))
        n, w = 32, 4
        M = jax.random.normal(jax.random.PRNGKey(3), (n, n), jnp.float64)
        C = 0.5 * (M + M.T)
        de.reset_dispatch_count()
        W, Q1 = de.dist_reduce_to_band(mesh, C, w)
        jax.block_until_ready((W, Q1))
        fused = de.dispatch_count()
        assert fused <= TT1_FUSED_MAX_DISPATCHES, fused
        Wl, Q1l = np.asarray(W), np.asarray(Q1)
        Wsym = 0.5 * (Wl + Wl.T)
        # invariants: orthogonal Q1, exact band mask, Q1^T C Q1 = W
        np.testing.assert_allclose(Q1l.T @ Q1l, np.eye(n), atol=1e-12)
        d = np.abs(np.arange(n)[:, None] - np.arange(n)[None, :])
        assert np.abs(np.where(d > w, Wl, 0.0)).max() == 0.0
        np.testing.assert_allclose(Q1l.T @ np.asarray(C) @ Q1l, Wsym,
                                   atol=1e-11)
        # numerical parity with the local fused sweep (same reflectors,
        # same SYR2K update form -> agreement far below the invariant tol)
        band = reduce_to_band(C, w=w)
        np.testing.assert_allclose(Wsym, np.asarray(unpack_band(band.Wb)),
                                   atol=1e-11)
        np.testing.assert_allclose(np.abs(Q1l), np.abs(np.asarray(band.Q1)),
                                   atol=1e-10)
        de.reset_dispatch_count()
        Ws, Q1s = de.dist_reduce_to_band_stepwise(mesh, C, w)
        jax.block_until_ready((Ws, Q1s))
        n_panels = len(range(0, n - w - 1, w))
        assert de.dispatch_count() >= (
            TT1_STEPWISE_DISPATCHES_PER_PANEL * n_panels), de.dispatch_count()
        np.testing.assert_allclose(np.asarray(Ws), Wsym, atol=1e-11)
        # odd n (not divisible by the 2 row shards): the identity-padding
        # path must stay one fused dispatch and match the local reduction
        n2 = 33
        M2 = jax.random.normal(jax.random.PRNGKey(4), (n2, n2), jnp.float64)
        C2 = 0.5 * (M2 + M2.T)
        de.reset_dispatch_count()
        W2, Q12 = de.dist_reduce_to_band(mesh, C2, w)
        jax.block_until_ready((W2, Q12))
        assert de.dispatch_count() <= TT1_FUSED_MAX_DISPATCHES, (
            de.dispatch_count())
        assert W2.shape == (n2, n2) and Q12.shape == (n2, n2)
        W2l, Q12l = np.asarray(W2), np.asarray(Q12)
        band2 = reduce_to_band(C2, w=w)
        np.testing.assert_allclose(0.5 * (W2l + W2l.T),
                                   np.asarray(unpack_band(band2.Wb)),
                                   atol=1e-11)
        np.testing.assert_allclose(Q12l.T @ Q12l, np.eye(n2), atol=1e-12)
        print("DIST_TT1_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_TT1_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_distributed_tt_parity_two_device():
    """Fast lane: the distributed two-stage (TT) pipeline on a 2-device
    (1, 2) mesh matches the local TT eigenvalues to 1e-6. (n kept small:
    the replicated bulge chase dominates subprocess time; the 8-device
    nightly run covers the larger shape.)"""
    _run_tt_parity(2, (1, 2), n=32, s=4, w=4)


_INVERT_PARITY_TEMPLATE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.data.problems import md_like
    from repro.core import solve
    from repro.core.residuals import accuracy_report
    from repro.dist.partitioning import make_mesh
    mesh = make_mesh({mesh_shape}, ("data", "model"))
    prob = md_like({n})  # A SPD: the inverse-pair trick is valid
    variant = {variant!r}
    ref = solve(prob.A, prob.B, 4, variant=variant, invert=True,
                band_width=4, max_restarts=300)
    res = solve(prob.A, prob.B, 4, variant=variant, invert=True,
                band_width=4, max_restarts=300, mesh=mesh)
    np.testing.assert_allclose(np.asarray(res.evals),
                               np.asarray(ref.evals), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res.evals),
                               np.asarray(prob.exact_evals[:4]),
                               rtol=1e-7, atol=1e-9)
    # the epilogue must hand back ORIGINAL-problem metrics:
    # unit-B-norm columns and a small generalized residual
    acc = accuracy_report(prob.A, prob.B, res.X, res.evals)
    assert float(acc.relative_residual) < 1e-9, variant
    colnorm = np.einsum("is,is->s", np.asarray(res.X),
                        np.asarray(prob.B @ res.X))
    np.testing.assert_allclose(colnorm, 1.0, rtol=1e-10)
    print("DIST_INVERT_OK")
"""


def _run_invert_parity(variant, n=48, mesh_shape=(1, 2)):
    """invert=True combined with mesh= dispatch: the distributed KE/TT
    paths return through ``_finalize``'s inverse-pair epilogue (1/lam,
    re-sort, b_normalize against the original B). Parity against the local
    variant on a 2-device mesh — previously untested."""
    code = textwrap.dedent(_INVERT_PARITY_TEMPLATE.format(
        variant=variant, n=n, mesh_shape=mesh_shape))
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_INVERT_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_distributed_invert_parity_two_device_ke():
    _run_invert_parity("KE")


def test_distributed_ke_pads_uneven_n_two_device():
    """An n that does not tile the mesh (47 over two row shards) is padded
    — A with zeros, B with an identity block — and solves to the same
    eigenpairs as the local KE."""
    _run_invert_parity("KE", n=47, mesh_shape=(2, 1))


@pytest.mark.slow
def test_distributed_invert_parity_two_device_tt():
    """TT variant of the invert parity check (the replicated bulge chase
    makes this the pricier half; nightly)."""
    _run_invert_parity("TT")


def test_distributed_ke_collective_and_dispatch_budget_two_device():
    """Communication-avoiding regression pins, fast lane (2 devices):

    1. The registered ``dist/ke_restart_program`` budget contract holds on
       both mesh orientations — at most 2 collectives per block step
       (psum + all_gather), an exact static total, zero dynamic whiles —
       and its StableHLO cross-reference stays within the published
       ``KE_HLO_*`` caps (the whole segment is one fori_loop, so the body
       appears once in the text). A regression to per-matvec or per-column
       communication would break the contract.
    2. The host issues at most ``ke_dispatch_budget(n_restart)`` dispatches
       for the whole Krylov stage (one fused program per restart + prep).
    3. The solve actually converges at the benchmark settings (invert +
       tol=1e-9) and matches the exact spectrum.
    """
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.analysis.static_audit import (
            AuditSpec, KE_HLO_ALL_GATHER_MAX, KE_HLO_ALL_REDUCE_MAX,
            check_entry, get_entry, ke_dispatch_budget, register_all)
        from repro.data.problems import md_like
        from repro.dist import eigensolver as de

        spec = AuditSpec()                  # n=64, s=4, p=4, m=24
        n, s, p, m = spec.n, spec.s, spec.p, spec.m
        prob = md_like(n)
        for shape in ((1, 2), (2, 1)):
            from repro.dist.partitioning import make_mesh
            mesh = make_mesh(shape, ("data", "model"))
            # 1. the registered budget contract, on this orientation
            register_all(spec, mesh=mesh)
            rep = check_entry(get_entry("dist/ke_restart_program"))
            assert rep.ok, (shape, rep.violations)
            hlo = rep.profiles[0].hlo_counts
            assert hlo["stablehlo.all_reduce"] <= KE_HLO_ALL_REDUCE_MAX, hlo
            assert hlo["stablehlo.all_gather"] <= KE_HLO_ALL_GATHER_MAX, hlo
            # 2 + 3. dispatch budget and convergence at benchmark settings
            de.reset_dispatch_count()
            evals, X, info = de.solve_ke_distributed(
                mesh, prob.A, prob.B, s=s, m=m, p=p, tol=1e-9,
                filter_degree=8, invert=True, return_info=True)
            assert info["converged"], info
            assert de.dispatch_count() <= ke_dispatch_budget(
                info["n_restart"]), (de.dispatch_count(), info)
            np.testing.assert_allclose(np.asarray(evals),
                                       np.asarray(prob.exact_evals[:s]),
                                       rtol=1e-8, atol=1e-10)
        print("DIST_KE_BUDGET_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_KE_BUDGET_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_distributed_tt3_spectrum_partition_two_device():
    """Fast lane: the spectrum-partitioned TT3 (``dist_tridiag_eig``) on a
    2-device mesh

    (a) matches the replicated 'batched' path — lam BITWISE, Z to 1e-12
        (the column-norm reduction may reassociate at ulp level on the
        narrow local slices) — for even and uneven (padded) index counts
        and shuffled ``ks``,
    (b) satisfies the registered ``dist/tt3_program`` contract at this
        shape — exactly ``tt3_dist_collectives(iters)`` static collectives
        (1 lam all_gather + one Z all_gather per refinement round) with the
        ``TT3_HLO_ALL_GATHER_MAX`` StableHLO cross-reference — and
    (c) drives ``solve_tt_distributed``: sharded vs replicated TT3 end to
        end, Z assembled from per-shard index slices, err <= 1e-10.
    """
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.analysis.static_audit import (
            AuditSpec, TT3_HLO_ALL_GATHER_MAX, check_entry, get_entry,
            register_all, tt3_dist_collectives)
        from repro.core.tridiag_eig import eigh_tridiag_selected
        from repro.data.problems import md_like
        from repro.dist import eigensolver as de
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((2, 1), ("data", "model"))
        n = 48
        kd, ke = jax.random.split(jax.random.PRNGKey(0))
        d = jax.random.normal(kd, (n,), jnp.float64)
        e = jax.random.normal(ke, (n - 1,), jnp.float64)
        key = jax.random.PRNGKey(3)
        # (a) bitwise parity: even s, uneven s (pads in play), shuffled ks
        for ks in (jnp.arange(8), jnp.arange(7),
                   jnp.asarray([5, 1, 3, 0])):
            lam_d, Z_d = de.dist_tridiag_eig(mesh, d, e, ks, key)
            lam_r, Z_r = eigh_tridiag_selected(d, e, ks, key,
                                               method="batched")
            assert np.array_equal(np.asarray(lam_d), np.asarray(lam_r))
            assert np.abs(np.asarray(Z_d)
                          - np.asarray(Z_r)).max() <= 1e-12
        # (b) the registered collective contract at THIS shape: the lam
        # gather plus one Z gather per round, exactly — a regression to
        # per-shift or per-round-unrolled communication breaks the pin
        tt3_spec = AuditSpec(n=n, s=8)
        register_all(tt3_spec, mesh=mesh)
        rep = check_entry(get_entry("dist/tt3_program"))
        assert rep.ok, rep.violations
        assert rep.total_collectives == tt3_dist_collectives(
            tt3_spec.tt3_iters), rep.total_collectives
        hlo = rep.profiles[0].hlo_counts
        assert hlo["stablehlo.all_gather"] <= TT3_HLO_ALL_GATHER_MAX, hlo
        # (c) end to end: sharded vs replicated TT3 through the full
        # two-stage pipeline (s=3 exercises the uneven padding there too)
        prob = md_like(32)
        for s in (4, 3):
            evals_s, X_s, info_s = de.solve_tt_distributed(
                mesh, prob.A, prob.B, s, band_width=4, return_info=True)
            evals_r, X_r, info_r = de.solve_tt_distributed(
                mesh, prob.A, prob.B, s, band_width=4, return_info=True,
                shard_tt3=False)
            assert info_s["tt3_sharded"] and not info_r["tt3_sharded"]
            assert np.abs(np.asarray(evals_s)
                          - np.asarray(evals_r)).max() <= 1e-10
            assert np.abs(np.asarray(X_s) - np.asarray(X_r)).max() <= 1e-10
            np.testing.assert_allclose(np.asarray(evals_s),
                                       np.asarray(prob.exact_evals[:s]),
                                       rtol=1e-7, atol=1e-9)
        print("DIST_TT3_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_TT3_OK" in out.stdout, out.stdout + out.stderr[-3000:]


@pytest.mark.slow
def test_distributed_tt_parity_eight_device():
    """The full 8-device (4, 2) mesh variant of the TT parity check (TT3
    spectrum-partitioned over all 8 devices, s=4 < 8 so padding is live)."""
    _run_tt_parity(8, (4, 2), n=64, s=4, w=8)


@pytest.mark.slow
def test_distributed_ke_pipeline_end_to_end():
    """The full distributed KE solve matches the exact spectrum (8 devices).

    Runs at the settings where the MD generator actually converges — the
    paper's inverse-pair trick + tol=1e-9 (the machine-eps default
    criterion is unreachable on this log-spaced spectrum, and the old
    retire-at-max_restarts configuration is exactly what the block
    rework stopped racing) — and asserts convergence, not just accuracy.
    """
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.data.problems import md_like
        from repro.dist.eigensolver import solve_ke_distributed
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        prob = md_like(64)
        evals, X, info = solve_ke_distributed(mesh, prob.A, prob.B, s=4,
                                              m=24, tol=1e-9,
                                              max_restarts=300,
                                              invert=True,
                                              return_info=True)
        assert info["converged"], info
        np.testing.assert_allclose(np.asarray(evals),
                                   np.asarray(prob.exact_evals[:4]),
                                   rtol=1e-8, atol=1e-10)
        # residual of the generalized problem
        R = np.asarray(prob.A @ X - (prob.B @ X) * np.asarray(evals)[None, :])
        rel = np.linalg.norm(R) / np.linalg.norm(np.asarray(prob.A))
        assert rel < 1e-8, rel
        print("DIST_KE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_KE_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_distributed_ke_spans_counters_and_program_names_four_device():
    """The mesh path of ``core.solve`` on a 2x2 host mesh: its stage and
    Krylov spans nest inside ``repro.solve`` (one ``repro.ke.sync`` a
    restart), ``info["counters"]`` counts ``n_restart + 6`` host syncs
    (GS1 wait, two GS2 waits, a verdict a restart, the KE_iter and BT1
    waits and the output sentinel's fetch) and one dispatch a restart,
    and no program it lowers is named ``<unknown>`` or ``local``."""
    code = textwrap.dedent("""
        import glob, os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_enable_x64", True)
        from jax.profiler import ProfileData
        from repro.core import solve
        from repro.data.problems import md_like
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        prob = md_like(64)
        kw = dict(variant="KE", invert=True, tol=1e-9, krylov_block=4,
                  mesh=mesh)
        lowered = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, d, **a: lowered.append(a.get("fun_name", "")))
        first = solve(prob.A, prob.B, 4, **kw)
        assert first.info["counters"]["compiles"] >= 5, first.info
        for name in ("jit(cholesky_blocked)", "jit(_trsm_lt_blocked)",
                     "jit(_trsm_l_blocked)", "jit(ke_restart)"):
            assert name in lowered, (name, sorted(set(lowered)))
        bad = [f for f in lowered if "<unknown>" in f or "(local)" in f]
        assert not bad, bad
        log_dir = tempfile.mkdtemp()
        with jax.profiler.trace(log_dir):
            res = solve(prob.A, prob.B, 4, **kw)
        c, n_restart = res.info["counters"], res.info["n_restart"]
        assert res.info["converged"] and n_restart >= 2, res.info
        assert c == {"host_syncs": n_restart + 6, "dispatches": n_restart,
                     "compiles": 0, "split_operator": 0}, (c, n_restart)
        path = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")[0]
        spans = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for p in ProfileData.from_file(path).planes for ln in p.lines
            for e in ln.events if e.name.startswith("repro."))
        names = [sp[2] for sp in spans]
        top = spans[names.index("repro.solve")]
        ke = spans[names.index("repro.KE_iter")]
        assert all(top[0] <= a and b <= top[1] for a, b, _, _ in spans)
        for stage in ("GS1", "GS2", "KE_iter", "BT1", "finalize"):
            assert "repro." + stage in names, names
        assert names.count("repro.GS2") == 2
        inner = [sp for sp in spans if sp[2].startswith("repro.ke.")]
        assert {sp[2] for sp in inner} == {"repro.ke.place", "repro.ke.prep",
                                          "repro.ke.restart",
                                          "repro.ke.sync"}, names
        assert all(ke[0] <= a and b <= ke[1] for a, b, _, _ in inner)
        assert [sp[3]["restart"] for sp in inner
                if sp[2] == "repro.ke.sync"] == list(range(n_restart))
        print("DIST_SPANS_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_SPANS_OK" in out.stdout, out.stdout + out.stderr[-3000:]
