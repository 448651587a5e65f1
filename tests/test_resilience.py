"""Failure containment: chaos suite + the resilience satellites.

Every injected fault must end in a documented recovery (a rung in
``info['recovery']``) or a diagnosed ``SolverError`` — never a silent
NaN eigenpair. The fault harness is ``repro.resilience.faults``
(seeded, deterministic); the ladder is ``repro.resilience.recovery``.

Fast-lane tests cover the adversarial-pencil regressions, the checkpoint
round-trip and the straggler/elastic compose; ``-m chaos`` (the nightly
chaos lane) additionally selects the fault-injection tests; the
multi-device preemption drill is ``slow`` (subprocess with forced host
devices).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import solve, solve_batched
from repro.data.problems import md_like
from repro.resilience import SolverError, cholesky_shift_taus
from repro.resilience import faults
from repro.resilience.faults import (ForceNonconverge, NanPoison, inject,
                                     near_breakdown_pencil, nonspd_pencil,
                                     slow_then_lost_trace)
from repro.serve.eigen_engine import EigenEngine

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, S = 32, 3

VARIANTS = ("TD", "TT", "KE", "KI")
PRECISIONS = ("fp64", "mixed", "fast")


# --------------------------------------------------------------------------
# satellite 1: adversarial pencils (regression, fast lane)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_nonspd_b_raises_diagnosed(variant, precision):
    """Indefinite B (min eig ~ -0.1, beyond every shift rung): every
    variant and precision must raise the diagnosed SolverError, with the
    exhausted shift ladder on record."""
    A, B = nonspd_pencil(N)
    with pytest.raises(SolverError) as exc:
        solve(jnp.asarray(A), jnp.asarray(B), S, variant=variant,
              precision=precision, on_failure="warn")
    d = exc.value.diagnosis
    assert d["stage"] == "GS1"
    assert d["reason"] == "cholesky_breakdown"
    assert d["hint"]
    # one failed rung per shift tau, all on the trail
    shift_rungs = [r for r in d["recovery"]
                   if r["action"] == "cholesky_shift"]
    assert len(shift_rungs) == len(cholesky_shift_taus())
    assert all(r["outcome"] == "failed" for r in shift_rungs)
    json.dumps(d)                                  # diagnosis is JSON-clean


@pytest.mark.parametrize("variant", ["TD", "TT"])
def test_roundoff_indefinite_recovers_via_shift(variant):
    """B with a tiny negative eigenvalue (-1e-8): GS1 breaks down, the
    1e-6 relative shift rung rescues it, and the rung + shift land in
    info — recovery, not silence."""
    A, B = nonspd_pencil(N, min_eig=-1e-8)
    res = solve(jnp.asarray(A), jnp.asarray(B), S, variant=variant,
                on_failure="warn")
    assert np.all(np.isfinite(np.asarray(res.evals)))
    assert np.all(np.isfinite(np.asarray(res.X)))
    assert res.info["health"]["healthy"] is True
    assert res.info["gs1_shift"] > 0.0
    rungs = [r for r in res.info["recovery"]
             if r["action"] == "cholesky_shift"]
    assert rungs and rungs[-1]["outcome"] == "recovered"


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_near_breakdown_no_silent_nan(variant, precision):
    """cond(B) ~ 1e10: whatever happens — clean solve, shift rescue or a
    diagnosed failure — the caller never sees a silent NaN eigenpair."""
    A, B = near_breakdown_pencil(N)
    try:
        res = solve(jnp.asarray(A), jnp.asarray(B), S, variant=variant,
                    precision=precision, on_failure="warn",
                    max_restarts=80)
    except SolverError as err:
        assert err.diagnosis["reason"] in (
            "cholesky_breakdown", "nonfinite_stage", "nonfinite_output")
        return
    assert np.all(np.isfinite(np.asarray(res.evals)))
    assert np.all(np.isfinite(np.asarray(res.X)))
    assert "health" in res.info and "recovery" in res.info


# --------------------------------------------------------------------------
# chaos: stage-targeted NaN poisoning
# --------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("stage,kwargs", [
    ("GS1", dict(variant="TD")),
    ("GS2", dict(variant="TD")),
    ("TD1", dict(variant="TD")),
    ("TT1", dict(variant="TT")),
    ("KE_iter", dict(variant="KE", invert=True)),
    ("KI_iter", dict(variant="KI", invert=True)),
])
def test_persistent_poison_is_diagnosed(stage, kwargs):
    """A persistent NaN fault at any stage ends in SolverError naming a
    stage at-or-upstream-of the sentinel that caught it."""
    prob = md_like(N)
    with inject(NanPoison(stage)):
        with pytest.raises(SolverError) as exc:
            solve(prob.A, prob.B, S, on_failure="warn", **kwargs)
    d = exc.value.diagnosis
    assert d["reason"] == "nonfinite_stage"
    assert d["stage"] == stage
    assert d.get("health", {}).get("healthy") is False
    assert d["health"]["first_unhealthy_stage"] == stage


@pytest.mark.chaos
def test_transient_poison_retried_under_recover():
    """once=True models a transient corruption: the recover ladder's
    retry rung re-runs with a fresh key and succeeds."""
    prob = md_like(N)
    with inject(NanPoison("GS2", once=True)):
        res = solve(prob.A, prob.B, S, variant="TD", on_failure="recover")
    assert res.info["health"]["healthy"] is True
    retries = [r for r in res.info["recovery"]
               if r["action"] == "transient_retry"]
    assert retries and retries[-1]["outcome"] == "recovered"
    np.testing.assert_allclose(np.asarray(res.evals),
                               np.asarray(md_like(N).exact_evals[:S]),
                               rtol=1e-7, atol=1e-9)


@pytest.mark.chaos
def test_persistent_poison_exhausts_retries():
    """The same fault, persistent: retries burn out and the error keeps
    the full trail (bounded ladder, no infinite retry loop)."""
    prob = md_like(N)
    with inject(NanPoison("GS2")):
        with pytest.raises(SolverError) as exc:
            solve(prob.A, prob.B, S, variant="TD", on_failure="recover",
                  max_retries=2)
    trail = exc.value.diagnosis["recovery"]
    assert sum(1 for r in trail
               if r["action"] == "transient_retry") == 2


# --------------------------------------------------------------------------
# chaos: forced nonconvergence -> escalate -> TT fallback
# --------------------------------------------------------------------------

@pytest.mark.chaos
def test_nonconvergence_ladder_falls_back_to_tt():
    prob = md_like(N)
    with inject(ForceNonconverge()):
        res = solve(prob.A, prob.B, S, variant="KE", invert=True,
                    on_failure="recover")
    actions = [r["action"] for r in res.info["recovery"]]
    assert "escalate_krylov" in actions
    assert "fallback_variant" in actions
    assert res.info["variant"] == "TT"
    assert res.info.get("converged", True)   # direct TT: no Krylov budget
    np.testing.assert_allclose(np.asarray(res.evals),
                               np.asarray(prob.exact_evals[:S]),
                               rtol=1e-7, atol=1e-9)


@pytest.mark.chaos
def test_nonconvergence_warn_mode_retires_with_warning():
    prob = md_like(N)
    with inject(ForceNonconverge()):
        res = solve(prob.A, prob.B, S, variant="KE", invert=True,
                    on_failure="warn")
    assert not res.info["converged"]
    assert any("UNCONVERGED" in w for w in res.info["warnings"])
    assert res.info["recovery"] == []          # warn never climbs the ladder


# --------------------------------------------------------------------------
# chaos: serving-engine quarantine + dead-letter
# --------------------------------------------------------------------------

@pytest.mark.chaos
def test_engine_quarantines_and_recovers_unconverged_lanes():
    """Lanes that miss the bucket's restart budget are retried
    individually up the ladder and retire healthy."""
    probs = [md_like(N, key=jax.random.PRNGKey(900 + i)) for i in range(2)]
    eng = EigenEngine(slots=2, bucket_shapes=[N], variant="KE",
                      max_restarts=1, on_failure="recover")
    uids = {eng.submit(p.A, p.B, S): p for p in probs}
    done = eng.run_until_drained()
    assert len(done) == len(probs) and not eng.dead_letters
    summary = eng.summary()
    assert summary["quarantined"] == len(probs)
    for req in done:
        assert req.info["path"] == "quarantine"
        assert req.info["converged"]
        assert req.info["health"]["healthy"] is True
        p = uids[req.uid]
        np.testing.assert_allclose(req.evals,
                                   np.asarray(p.exact_evals[:S]),
                                   rtol=1e-7, atol=1e-9)


@pytest.mark.chaos
def test_engine_dead_letters_unrecoverable_lane():
    """A non-SPD pencil poisons its bucket lane; the quarantine retries
    end in a dead letter carrying the diagnosis, the healthy lane
    retires normally — no silent drops either way."""
    good = md_like(N, key=jax.random.PRNGKey(31))
    A_bad, B_bad = nonspd_pencil(N)
    eng = EigenEngine(slots=2, bucket_shapes=[N], variant="TD",
                      on_failure="recover", max_retries=1)
    uid_good = eng.submit(good.A, good.B, S)
    uid_bad = eng.submit(jnp.asarray(A_bad), jnp.asarray(B_bad), S)
    done = eng.run_until_drained()
    assert {r.uid for r in done} == {uid_good}
    assert [r.uid for r in eng.dead_letters] == [uid_bad]
    dead = eng.dead_letters[0]
    assert dead.info["path"] == "dead_letter"
    assert dead.info["health"]["healthy"] is False
    assert dead.info["dead_letter"]["reason"] == "cholesky_breakdown"
    json.dumps(dead.info)
    # the no-silent-drop invariant, stated as the summary reports it
    summary = eng.summary()
    assert summary["dead_letter_uids"] == [uid_bad]
    assert summary["requests"] == 2


@pytest.mark.chaos
def test_batched_surfaces_unhealthy_pencils():
    """solve_batched itself (no engine): a poisoned pencil in the stack
    flips its per-pencil healthy flag and the batch-level warning."""
    probs = [md_like(N, key=jax.random.PRNGKey(70 + i)) for i in range(3)]
    A = jnp.stack([p.A for p in probs])
    B_bad = np.asarray(probs[1].B).copy()
    B_bad[0, 0] = np.nan
    B = jnp.stack([probs[0].B, jnp.asarray(B_bad), probs[2].B])
    res = solve_batched(A, B, S, variant="TD")
    healthy = np.asarray(res.healthy)
    assert not healthy[1] and healthy[0] and healthy[2]
    assert res.info["n_unhealthy"] == 1
    assert any("non-finite" in w.lower() for w in res.info["warnings"])


# --------------------------------------------------------------------------
# satellite 3: orphaned robustness modules, wired
# --------------------------------------------------------------------------

def test_checkpoint_roundtrips_thick_restart_state(tmp_path):
    from repro.dist import checkpoint as ckpt
    V = jnp.asarray(np.random.default_rng(0).standard_normal((16, 6)))
    T = jnp.asarray(np.random.default_rng(1).standard_normal((6, 6)))
    ckpt.save(str(tmp_path), 3, {"V": V, "T": T},
              extra={"kind": "ke_dist", "j": 2, "n_matvec": 40}, keep=2)
    ckpt.save(str(tmp_path), 4, {"V": V + 1.0, "T": T},
              extra={"kind": "ke_dist", "j": 3, "n_matvec": 50}, keep=2)
    like = {"V": jnp.zeros_like(V), "T": jnp.zeros_like(T)}
    step, tree, extra = ckpt.load_latest(str(tmp_path), like)
    assert step == 4 and extra["j"] == 3 and extra["n_matvec"] == 50
    np.testing.assert_array_equal(np.asarray(tree["V"]), np.asarray(V + 1.0))
    np.testing.assert_array_equal(np.asarray(tree["T"]), np.asarray(T))


def test_straggler_and_elastic_compose_on_host_loss():
    """The simulated slow-then-lost host trace drives the monitor's
    rebalance while the host limps, then plan_remesh once it is lost."""
    from repro.dist.elastic import plan_remesh
    from repro.dist.straggler import StragglerMonitor
    n_hosts, slow = 4, 2
    trace = slow_then_lost_trace(n_hosts=n_hosts, slow_host=slow)
    mon = StragglerMonitor(n_hosts)
    survivors = n_hosts
    for step in trace:
        if step["lost"]:
            survivors = n_hosts - len(step["lost"])
            break
        for h, t in enumerate(step["times"]):
            mon.record(h, t)
    # while limping: flagged as a straggler, rebalanced below fair share
    assert mon.stragglers() == [slow]
    plan = mon.rebalance_plan(microbatches_per_host=6)
    assert sum(plan.values()) == n_hosts * 6
    assert plan[slow] < 6
    assert all(plan[h] >= 6 for h in range(n_hosts) if h != slow)
    # once lost: the remesh plan drops to the survivors, no devices idle
    rp = plan_remesh(survivors, 1)
    assert rp.new_shape == (survivors, 1)
    assert rp.n_used == survivors and rp.n_dropped == 0


# --------------------------------------------------------------------------
# sentinel budget proof (rides the session audit fixture)
# --------------------------------------------------------------------------

def test_sentinels_are_fused_and_dispatch_free(assert_program_budget):
    """The acceptance criterion in auditor terms: the sentinel-bearing
    contracts hold with a 0-dispatch sentinel allowance, and the fused
    is_finite sites are really in the lowered programs."""
    from repro.analysis.static_audit.contracts import (
        SENTINEL_EXTRA_DISPATCHES)
    assert SENTINEL_EXTRA_DISPATCHES == 0
    for name, min_sites in [("resilience/stage_sentinels", 2),
                            ("core/lanczos_solve_jit", 1),
                            ("serve/solve_batched_TD", 1),
                            ("serve/solve_batched_KE", 1),
                            ("dist/ke_restart_program", 1)]:
        entry = assert_program_budget(name)
        assert entry["isfinite_sites"] >= min_sites, name
        assert entry["contract"]["sentinel_extra_dispatches"] == 0, name


def test_audit_payload_reports_sentinel_summary(audit_report):
    sen = audit_report["sentinels"]
    assert sen["ok"] is True
    assert sen["entries"] >= 5
    assert sen["isfinite_sites"] >= sen["entries"]
    assert sen["extra_dispatches_allowed"] == 0


# --------------------------------------------------------------------------
# chaos (nightly): distributed preemption drill
# --------------------------------------------------------------------------

_PREEMPT_DRILL = textwrap.dedent("""
    import os, shutil, tempfile
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2")
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.data.problems import md_like
    from repro.dist.eigensolver import solve_ke_distributed
    from repro.dist.elastic import plan_remesh
    from repro.resilience.faults import SimulatedPreemption

    prob = md_like(48, key=jax.random.PRNGKey(5))
    from repro.dist.partitioning import make_mesh
    mesh = make_mesh((2, 1), ("data", "model"))
    kw = dict(s=4, p=4, m=8, invert=True, max_restarts=200,
              return_info=True)

    lam_ref, _, info_ref = solve_ke_distributed(mesh, prob.A, prob.B, **kw)
    assert info_ref["healthy"]

    ckdir = tempfile.mkdtemp()
    try:
        try:
            solve_ke_distributed(mesh, prob.A, prob.B,
                                 checkpoint_dir=ckdir, checkpoint_every=1,
                                 preempt_after=2, **kw)
            raise SystemExit("no preemption raised")
        except SimulatedPreemption as e:
            print("PREEMPTED_AT", e.at_restart)
        # one host lost: resume from the checkpoint on the shrunken mesh
        plan = plan_remesh(1, 1)
        from repro.dist.partitioning import make_mesh
        mesh_small = make_mesh(plan.new_shape, ("data", "model"))
        lam2, _, info2 = solve_ke_distributed(
            mesh_small, prob.A, prob.B, checkpoint_dir=ckdir,
            resume=True, **kw)
        assert info2["healthy"] and info2["resumed_from"] >= 0
        err = float(np.max(np.abs(np.asarray(lam2) - np.asarray(lam_ref))))
        print("PARITY_ERR", err)
        assert err < 1e-12, err
        print("DRILL_OK")
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
""")


@pytest.mark.slow
@pytest.mark.chaos
def test_dist_ke_preemption_drill_resumes_to_parity():
    """Checkpoint at restart boundaries, preempt, resume on a
    plan_remesh-shrunken mesh: eigenvalues match the uninterrupted run
    to 1e-12 (the collectives' roundoff floor)."""
    out = subprocess.run(
        [sys.executable, "-c", _PREEMPT_DRILL], capture_output=True,
        text=True, env=dict(os.environ, PYTHONPATH="src"), cwd=_ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "DRILL_OK" in out.stdout


# --------------------------------------------------------------------------
# faults module hygiene
# --------------------------------------------------------------------------

def test_inject_disarms_on_exit():
    assert faults.active("nan") is None
    with inject(NanPoison("GS1")):
        assert faults.active("nan") is not None
        with pytest.raises(RuntimeError):
            with inject(ForceNonconverge()):
                assert faults.active("nan") is not None
                assert faults.active("nonconverge") is not None
                raise RuntimeError("boom")
        assert faults.active("nonconverge") is None
    assert faults.active("nan") is None


def test_nan_poison_is_deterministic():
    f1 = NanPoison("GS1", seed=7)
    f2 = NanPoison("GS1", seed=7)
    x = np.ones((8, 8))
    np.testing.assert_array_equal(f1.apply("GS1", x), f2.apply("GS1", x))
    # untouched stage passes through by identity
    assert f1.apply("GS2", x) is x
