"""Top-level GSYEIG driver: A X = B X Lambda, s << n wanted eigenpairs.

Four variants, exactly the paper's:
  TD — Cholesky + standard form + direct tridiagonalization + bisect/invit
  TT — Cholesky + standard form + two-stage (band) reduction + bisect/invit
  KE — Cholesky + standard form + thick-restart Lanczos on explicit C
  KI — Cholesky + Lanczos on implicit C = U^{-T} A U^{-1} (no GS2)

`which='smallest'|'largest'` selects the end of the spectrum;
`invert=True` applies the paper's MD trick (solve the inverse pair (B, A)
for its largest eigenpairs — valid when A is also SPD — and map back).
`variant='auto'` routes through the cost model in
``repro.analysis.variant_model`` (see ``info['router']`` for the decision).

Every stage is individually jitted and timed (paper Tables 2/6 keys).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.resilience import faults
from repro.resilience.health import (array_finite, chol_health, host_finite,
                                     verdict_from_stages)
from repro.resilience.recovery import (SolverError, cholesky_shift_taus,
                                       rung, validate_on_failure)

from .back_transform import back_transform_generalized
from .cholesky import cholesky_blocked, cholesky_upper, diag_shifted
from . import looped
from .instrument import count, counting, fetch, span, timed
from .lanczos import default_subspace, lanczos_solve
from .operators import ExplicitC, ImplicitC, SplitC, split_c
from .precision import (compute_dtype, ensure_strong, exact_matmuls,
                        validate_precision)
from .refinement import REFINE_TOL, refine_eigenpairs
from . import sbr as _sbr
from .sbr import apply_q2, band_chase, default_n_chunks, reduce_to_band
from .standard_form import to_standard_sygst, to_standard_two_trsm
from .tridiag import apply_q, tridiagonalize, tridiagonalize_blocked
from .tridiag_eig import eigh_tridiag_selected

VARIANTS = ("TD", "TT", "KE", "KI")


@dataclass
class GSyEigResult:
    evals: jax.Array                 # (s,) ascending (original problem)
    X: jax.Array                     # (n, s) B-orthonormal eigenvectors
    stage_times: Dict[str, float] = field(default_factory=dict)
    info: Dict[str, Any] = field(default_factory=dict)


# module-level jitted stages (cached across driver calls with equal shapes).
# GS1/GS2 carry FUSED health sentinels: the isfinite/pivot reductions are
# part of the same program as the factorization they guard, so stage
# verdicts cost zero extra dispatches (the auditor's
# ``resilience/stage_sentinels`` entry pins this)
def _chol_fused(B):
    U = cholesky_upper(B)
    ok, min_diag = chol_health(U)
    return U, ok, min_diag


def _chol_blocked_fused(B, block):
    U = cholesky_blocked(B, block)
    ok, min_diag = chol_health(U)
    return U, ok, min_diag


def _chol_ladder_fused(B, taus):
    """Degradation ladder, rung 1, as ONE program: Cholesky every
    diagonally-shifted candidate ``B + tau*max|diag B|*I`` in a single
    vmapped dispatch, returning the stacked factors and per-rung health
    flags. Rung-by-rung retries would cost a dispatch plus a host sync
    per tau; fusing the ladder makes even a fully exhausted ladder cost
    one dispatch and one fetch, which is what keeps failed lanes from
    sinking healthy serving throughput (the chaos bench gates this)."""
    def one(tau):
        U = cholesky_upper(diag_shifted(B, tau))
        ok, _ = chol_health(U)
        return U, ok
    return jax.vmap(one)(taus)


def _gs2_trsm_fused(A, U):
    C = to_standard_two_trsm(A, U)
    return C, array_finite(C)


def _gs2_trsm_split(A, U):
    """GS2 for KE on emulated f64: C in its int8 split form, with the
    verdict taken on C (an int8 slice holds no NaN). C is this program's
    scratch only, so C and its split are never live together."""
    C, ok = _gs2_trsm_fused(A, U)
    return split_c(C), ok


def _gs2_sygst_fused(A, U, block):
    C = to_standard_sygst(A, U, block=block)
    return C, array_finite(C)


_jit_chol = jax.jit(_chol_fused)
_jit_chol_blocked = jax.jit(_chol_blocked_fused, static_argnames=("block",))
_jit_chol_ladder = jax.jit(_chol_ladder_fused)
_jit_gs2_trsm = jax.jit(_gs2_trsm_fused)
_jit_gs2_split = jax.jit(_gs2_trsm_split)
_jit_gs2_sygst = jax.jit(_gs2_sygst_fused, static_argnames=("block",))
_jit_td1 = jax.jit(tridiagonalize)
_jit_td1_blocked = jax.jit(tridiagonalize_blocked, static_argnames=("panel",))
_jit_td3 = jax.jit(apply_q)
# TT4: back-transform the (n, s) Ritz slab through the recorded TT2
# rotation stream, then one GEMM against the explicit Q1 — no (n, n) Q2
_jit_tt4 = jax.jit(lambda chase, Q1, Z, w: Q1 @ apply_q2(chase, Z, w),
                   static_argnames=("w",))
_jit_bt1 = jax.jit(back_transform_generalized)


def _solve_once(
    A: jax.Array,
    B: jax.Array,
    s: int,
    variant: str = "TD",
    which: str = "smallest",
    invert: bool = False,
    gs2: str = "trsm",          # 'trsm' (2n^3, paper's pick) or 'sygst' (n^3)
    gs1: str = "fused",         # 'fused' (DPOTRF analogue) or 'blocked'
    td1: str = "unblocked",     # 'unblocked' (BLAS-2 DSYTRD) or 'blocked'
    band_width: int = 16,
    block: int = 256,
    m: int | None = None,
    tol: float = 0.0,
    max_restarts: int = 500,
    use_kernel: bool = False,
    key: jax.Array | None = None,
    mesh=None,
    clustered: bool = False,
    machine=None,
    krylov_block: int | None = None,
    filter: int | None = None,        # noqa: A002 — the paper-facing name
    precision: str = "fp64",
    refine: bool | None = None,
    refine_tol: float = REFINE_TOL,
    refine_max_steps: int = 60,
    on_failure: str = "warn",
    recovery: list | None = None,
) -> GSyEigResult:
    """One attempt of the pipeline (the public ``solve`` wraps this with
    the degradation ladder). Stage health verdicts land in
    ``info['_stage_health']`` for the wrapper to fold into
    ``info['health']``; a breakdown or non-finite stage raises a
    diagnosed ``SolverError`` unless ``on_failure == 'ignore'``.

    `mesh=` (a jax.sharding.Mesh with a 'model' axis plus data axes)
    dispatches the KE and TT variants onto the distributed pipelines in
    ``repro.dist.eigensolver`` — same driver logic, every stage routed
    through ``repro.dist.sharded_la`` (KE: every matvec a ``dist_symv``;
    TT: ELPA2-style distributed two-stage band reduction).

    ``variant='auto'`` asks the flop/bandwidth cost model in
    ``repro.analysis.variant_model`` to pick the fastest variant for
    ``(n, s, band_width, mesh)``; the choice and its predicted-time table
    land in ``result.info['router']``. ``clustered=True`` tells the router
    the wanted end of the spectrum is clustered (DFT-like valence bands),
    which inflates the Lanczos iteration estimate — the decisive input for
    the KE-vs-TT crossover. ``machine=`` optionally supplies a (possibly
    measurement-calibrated, see ``MachineParams.from_artifact``)
    throughput model for the router.

    Krylov-side knobs (KE/KI only): ``krylov_block`` is the Lanczos block
    size p — each s-step segment advances p basis vectors with one fused
    multi-RHS matvec (``None`` = auto: 4 on a mesh, where the block
    structure is what buys the two-collectives-per-step schedule, 1
    locally). ``filter`` is the Chebyshev start-block filter degree
    (``None`` = auto: 16 when ``clustered=True`` — the clustered wanted
    end is exactly the case the filter exists for — else off; 0 forces
    off). Both land in ``result.info['krylov']``.

    ``precision=`` selects the compute dtype of the GEMM-heavy stages
    (``'fp64'`` default, ``'mixed'`` = fp32, ``'fast'`` = bf16 with fp32
    accumulation — see ``core.precision``); Cholesky/standard form, the
    tridiagonal eigensolve and all convergence math stay fp64. When the
    pipeline demoted anything, ``refine`` (default: on for non-fp64)
    runs fp64 iterative refinement of the returned eigenpairs against
    the *original* pencil until ``refine_tol`` (the Table-3 tolerance)
    is met — step count and residual trajectory land in
    ``result.info['refinement']``, the wall time in
    ``stage_times['RF']``."""
    validate_precision(precision)
    validate_on_failure(on_failure)
    if recovery is None:
        recovery = []
    stage_health: Dict[str, bool] = {}
    cdtype = compute_dtype(precision)
    demoted = precision != "fp64"
    if refine is None:
        refine = demoted
    # the declared working dtype is fp64: promote weak-typed (Python-
    # scalar-born) pencils on entry so the first downstream op cannot
    # silently decide the precision
    A = ensure_strong(A)
    B = ensure_strong(B)
    n = A.shape[0]
    times: Dict[str, float] = {}
    info: Dict[str, Any] = {"variant": variant, "n": n, "s": s,
                            "invert": invert, "which": which,
                            "precision": precision}
    # Krylov knobs resolve once, for the router and both solve paths
    p = krylov_block if krylov_block is not None else (
        4 if mesh is not None else 1)
    filter_degree = filter if filter is not None else (
        16 if clustered else 0)
    if variant == "auto":
        from repro.analysis.variant_model import (DISTRIBUTED_VARIANTS,
                                                  choose_variant)
        mesh_shape = tuple(mesh.devices.shape) if mesh is not None else None
        # any mesh (even a degenerate 1x1) narrows the candidates to the
        # variants the mesh dispatch below actually implements
        allow = DISTRIBUTED_VARIANTS if mesh is not None else None
        choice = choose_variant(n, s, band_width=band_width, m=m,
                                clustered=clustered, mesh_shape=mesh_shape,
                                allow=allow, machine=machine,
                                krylov_block=p, filter_degree=filter_degree,
                                precision=precision)
        variant = choice.variant
        info["variant"] = variant
        info["router"] = choice.as_json_dict()
    assert variant in VARIANTS, variant
    if key is None:
        key = jax.random.PRNGKey(20120520)
    if variant in ("KE", "KI"):
        info["krylov"] = {"p": int(p), "filter_degree": int(filter_degree)}

    A_orig, B_orig, which_orig = A, B, which
    refine_cfg = ({"tol": refine_tol, "max_steps": refine_max_steps}
                  if refine else None)
    if invert:
        # paper's MD trick: largest eigenpairs of the inverse pair (B, A)
        A, B = B, A
        which = "largest" if which == "smallest" else "smallest"

    if mesh is not None:
        if variant not in ("KE", "TT"):
            raise NotImplementedError(
                f"mesh= dispatch implements the KE and TT variants, "
                f"got {variant}")
        if gs2 != "trsm" or use_kernel:
            # the distributed pipelines are blocked-Cholesky + two-TRSM with
            # shard_map stages; reject flags they cannot honor rather than
            # silently substituting
            raise NotImplementedError(
                "mesh= implements gs2='trsm' without the Pallas kernel path")
        if variant == "KE":
            from repro.dist.eigensolver import solve_ke_distributed
            lam, X, dinfo = solve_ke_distributed(
                mesh, A, B, s, m=m, which=which, tol=tol,
                max_restarts=max_restarts, key=key, return_info=True,
                p=p, filter_degree=filter_degree, precision=precision)
        else:
            from repro.dist.eigensolver import solve_tt_distributed
            lam, X, dinfo = solve_tt_distributed(
                mesh, A, B, s, which=which, band_width=band_width, key=key,
                return_info=True, precision=precision)
        times.update(dinfo.pop("stage_times"))
        info.update(dinfo)
        stage_health[f"{variant}_dist"] = bool(dinfo.get("healthy", True))
        info["_stage_health"] = stage_health
        if not stage_health[f"{variant}_dist"] and on_failure != "ignore":
            raise SolverError(
                f"distributed {variant} produced a non-finite restart "
                f"state", stage=f"{variant}_dist", reason="nonfinite_stage",
                hint="probable GS1 breakdown (non-SPD B) or overflow in a "
                     "demoted stage; retry with precision='fp64' or check "
                     "the pencil", recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
        if not info.get("converged", True):
            info.setdefault("warnings", []).append(
                f"{variant} retired UNCONVERGED after "
                f"{info.get('n_restart', max_restarts)} restarts "
                f"(max_restarts={max_restarts}); eigenpairs are the best "
                f"Ritz approximations at exit")
        return _finalize(lam, X, A_orig, B_orig, which_orig, invert,
                         times, info, refine_cfg)

    # ---- GS1: B = U^T U --------------------------------------------------
    # the factor's health sentinel is fused into the same program (zero
    # extra dispatches); fetching the scalar verdict is a transfer the
    # stage timer's wait already paid for
    Bg = faults.poison_stage("GS1", B)
    chol_stage = (partial(_jit_chol_blocked, block=block)
                  if gs1 == "blocked" else _jit_chol)
    U, gs1_ok, _ = timed(times, "GS1", chol_stage, Bg)
    gs1_ok = bool(fetch(gs1_ok))
    if not gs1_ok and on_failure != "ignore":
        if not host_finite(fetch(Bg)):
            stage_health["GS1"] = False
            raise SolverError(
                "non-finite B entering GS1 (Cholesky)", stage="GS1",
                reason="nonfinite_stage",
                hint="the input pencil itself is corrupted; transient "
                     "corruption is retryable under on_failure='recover'",
                recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
        # degradation ladder, rung 1: relative diagonal-shift retries —
        # roundoff-level indefiniteness is recoverable, a truly non-SPD
        # B exhausts the ladder into a diagnosed SolverError. All rungs
        # run as ONE vmapped dispatch with a single fetch of the
        # per-rung verdicts, so an exhausted ladder stays cheap
        taus = cholesky_shift_taus()
        Us, oks = timed(times, "GS1", _jit_chol_ladder, Bg,
                        jnp.asarray(taus, dtype=Bg.dtype))
        oks = [bool(x) for x in fetch(oks)]
        for i, tau in enumerate(taus):
            if oks[i]:
                recovery.append(rung("cholesky_shift", "GS1", "recovered",
                                     tau=float(tau)))
                info["gs1_shift"] = float(tau)
                U = Us[i]
                gs1_ok = True
                break
            recovery.append(rung("cholesky_shift", "GS1", "failed",
                                 tau=float(tau)))
        if not gs1_ok:
            stage_health["GS1"] = False
            raise SolverError(
                "GS1 Cholesky breakdown: B is not SPD (all diagonal-shift "
                "rungs failed)", stage="GS1", reason="cholesky_breakdown",
                hint="check the B operand — the generalized problem "
                     "requires B symmetric positive definite; shifts up to "
                     f"tau={cholesky_shift_taus()[-1]:g}*max|diag B| did "
                     "not rescue it", recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
    stage_health["GS1"] = gs1_ok

    # ---- GS2: C = U^{-T} A U^{-1} (not for KI) ---------------------------
    # KE on emulated f64 (a TPU) multiplies C in int8 slices: GS2 returns
    # them instead of C, and each block step's product reads only them
    split = (variant == "KE" and gs2 == "trsm" and not demoted
             and looped.emulated_f64(A.dtype) and n > looped.MM_TILE)
    C = None
    if variant in ("TD", "TT", "KE"):
        Ag = faults.poison_stage("GS2", A)
        if split:
            C, gs2_ok = timed(times, "GS2", _jit_gs2_split, Ag, U)
        elif gs2 == "sygst":
            C, gs2_ok = timed(times, "GS2", _jit_gs2_sygst, Ag, U,
                              block=block)
        else:
            C, gs2_ok = timed(times, "GS2", _jit_gs2_trsm, Ag, U)
        gs2_ok = bool(fetch(gs2_ok))
        stage_health["GS2"] = gs2_ok
        if not gs2_ok and on_failure != "ignore":
            raise SolverError(
                "non-finite standard-form C after GS2", stage="GS2",
                reason="nonfinite_stage",
                hint="non-finite A, or U from a near-breakdown GS1; "
                     "transient corruption is retryable under "
                     "on_failure='recover'", recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())

    want_small = which == "smallest"
    if variant in ("TD", "TT"):
        ks = jnp.arange(s) if want_small else jnp.arange(n - s, n)
        # the reflector/rotation stages run in the compute dtype; the
        # tridiagonal eigensolve (TD2/TT3) is promoted back to fp64
        Cw = C if not demoted else C.astype(cdtype)
        if variant == "TD":
            Cw = faults.poison_stage("TD1", Cw)
            if td1 == "blocked":
                res = timed(times, "TD1", _jit_td1_blocked, Cw, panel=32)
            else:
                res = timed(times, "TD1", _jit_td1, Cw)
            # host-side sentinel on the small (n,)/(n-1,) tridiagonal
            # outputs the TD2 stage fetches anyway — zero dispatches (a
            # wrapping jit would break the composite stage's own timing)
            stage_health["TD1"] = host_finite(*fetch((res.d, res.e)))
            if not stage_health["TD1"] and on_failure != "ignore":
                raise SolverError(
                    "non-finite tridiagonal after TD1", stage="TD1",
                    reason="nonfinite_stage",
                    hint="corrupted C entering the reflector sweep "
                         "(demoted-stage overflow or upstream NaN)",
                    recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
            lam, Z = timed(times, "TD2", eigh_tridiag_selected,
                           res.d.astype(jnp.float64),
                           res.e.astype(jnp.float64), ks, key)
            Y = timed(times, "TD3", _jit_td3, res, Z.astype(cdtype))
        else:
            # TT1 split: the sweep is ONE compiled program (reduce_to_band
            # is internally jitted); record the ladder choice + dispatch
            # count so the stage timing is attributable
            Cw = faults.poison_stage("TT1", Cw)
            n_chunks = default_n_chunks(n, band_width)
            d0 = _sbr.dispatch_count()
            band = timed(times, "TT1", reduce_to_band, Cw, w=band_width,
                         n_chunks=n_chunks)
            info["tt1"] = {"n_chunks": int(n_chunks),
                           "dispatches": int(_sbr.dispatch_count() - d0)}
            # host sentinel on the (w+1, n) band the chase consumes
            stage_health["TT1"] = host_finite(fetch(band.Wb))
            if not stage_health["TT1"] and on_failure != "ignore":
                raise SolverError(
                    "non-finite band matrix after the TT1 sweep",
                    stage="TT1", reason="nonfinite_stage",
                    hint="corrupted C entering the panel sweep "
                         "(demoted-stage overflow or upstream NaN)",
                    recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
            chase = timed(times, "TT2", band_chase, band.Wb, band_width)
            stage_health["TT2"] = host_finite(*fetch((chase.d, chase.e)))
            if not stage_health["TT2"] and on_failure != "ignore":
                raise SolverError(
                    "non-finite tridiagonal after the TT2 chase",
                    stage="TT2", reason="nonfinite_stage",
                    hint="the rotation wavefront hit non-finite band "
                         "entries", recovery=recovery,
                    health=verdict_from_stages(stage_health).as_json_dict())
            lam, Z = timed(times, "TT3", eigh_tridiag_selected,
                           chase.d.astype(jnp.float64),
                           chase.e.astype(jnp.float64), ks, key)
            Y = timed(times, "TT4", _jit_tt4, chase, band.Q1,
                      Z.astype(cdtype), w=band_width)
        Y = Y.astype(jnp.float64)
    else:
        arp_which = "SA" if want_small else "LA"
        if split:
            # the poisoned scales reach every row the operator reads
            op = SplitC(C.Q, faults.poison_stage("KE_iter", C.scale))
            count("split_operator")
        elif variant == "KE":
            op = ExplicitC(faults.poison_stage("KE_iter", C))
        else:
            op = ImplicitC(faults.poison_stage("KI_iter", A), U)
        prefix = variant
        if m is None:
            m = default_subspace(s, n, p)
        elif p > 1 and m % p:
            m = -(-m // p) * p          # block-align a user-supplied m
        tol, max_restarts = faults.force_nonconverge(tol, max_restarts)
        lres = timed(times, f"{prefix}_iter", lanczos_solve, op, s,
                     which=arp_which, m=m, tol=tol,
                     max_restarts=max_restarts, key=key,
                     use_kernel=use_kernel, p=p,
                     filter_degree=filter_degree,
                     compute_dtype=cdtype if demoted else None)
        # plain-Python payloads only: info must survive json.dump in the
        # benchmark scripts (a jax array here broke them)
        info.update(n_matvec=int(lres.n_matvec), n_restart=int(lres.n_restart),
                    converged=bool(lres.converged),
                    resid_bounds=[float(r) for r in
                                  fetch(lres.resid_bounds)])
        stage_health[f"{prefix}_iter"] = bool(lres.healthy)
        if not lres.healthy and on_failure != "ignore":
            raise SolverError(
                f"{prefix} restart state went non-finite after "
                f"{int(lres.n_restart)} restarts", stage=f"{prefix}_iter",
                reason="nonfinite_stage",
                hint="NaN/inf in the Lanczos basis — corrupted operator "
                     "or demoted-matvec overflow; transient corruption is "
                     "retryable under on_failure='recover'",
                recovery=recovery,
                health=verdict_from_stages(stage_health).as_json_dict())
        if not lres.converged:
            info.setdefault("warnings", []).append(
                f"{prefix} retired UNCONVERGED after {int(lres.n_restart)} "
                f"restarts (max_restarts={max_restarts}); eigenpairs are "
                f"the best Ritz approximations at exit")
        lam, Y = lres.evals, lres.evecs
        # Lanczos returns wanted-first ordering; sort ascending like TD/TT
        order = jnp.argsort(lam)
        lam, Y = lam[order], Y[:, order]

    # ---- BT1: X = U^{-1} Y ----------------------------------------------
    X = timed(times, "BT1", _jit_bt1, U, Y)

    info["_stage_health"] = stage_health
    return _finalize(lam, X, A_orig, B_orig, which_orig, invert, times,
                     info, refine_cfg)


def _finalize(lam, X, A_orig, B_orig, which_orig: str, invert: bool,
              times: Dict[str, float], info: Dict[str, Any],
              refine_cfg: Dict[str, Any] | None = None) -> GSyEigResult:
    """Shared epilogue of the local and distributed paths: undo the
    inverse-pair trick, refine against the original fp64 pencil when
    asked, and total the stage timings."""
    if invert:
        with span("finalize"):
            lam = 1.0 / lam
            order = jnp.argsort(lam)
            lam, X = lam[order], X[:, order]
            # the inverse-pair solve returns A-orthonormal vectors;
            # renormalize each column to unit B-norm for the original
            # problem's metric
            from .residuals import b_normalize
            X = b_normalize(X, B_orig)

    if refine_cfg is not None:
        lam, X, rinfo = timed(times, "RF", refine_eigenpairs, A_orig, B_orig,
                              lam, X, which=which_orig, **refine_cfg)
        info["refinement"] = rinfo

    times["Tot."] = float(sum(v for k, v in times.items() if k != "Tot."))
    return GSyEigResult(evals=lam, X=X, stage_times=times, info=info)


@exact_matmuls
def solve(
    A: jax.Array,
    B: jax.Array,
    s: int,
    variant: str = "TD",
    which: str = "smallest",
    invert: bool = False,
    gs2: str = "trsm",
    gs1: str = "fused",
    td1: str = "unblocked",
    band_width: int = 16,
    block: int = 256,
    m: int | None = None,
    tol: float = 0.0,
    max_restarts: int = 500,
    use_kernel: bool = False,
    key: jax.Array | None = None,
    mesh=None,
    clustered: bool = False,
    machine=None,
    krylov_block: int | None = None,
    filter: int | None = None,        # noqa: A002 — the paper-facing name
    precision: str = "fp64",
    refine: bool | None = None,
    refine_tol: float = REFINE_TOL,
    refine_max_steps: int = 60,
    on_failure: str = "warn",
    max_retries: int = 2,
) -> GSyEigResult:
    """GSYEIG with failure containment: ``_solve_once`` (see its
    docstring for the solver knobs) wrapped in the degradation ladder of
    ``repro.resilience.recovery``.

    ``on_failure`` selects the policy:

      ``'warn'`` (default) — stage-boundary health sentinels diagnose
        failures: a GS1 breakdown tries the diagonal-shift rungs, any
        remaining non-finite stage or output raises ``SolverError``
        (never silent NaN eigenpairs); unconverged Krylov solves retire
        with a warning, exactly as before.
      ``'recover'`` — additionally climbs the ladder: transient
        non-finite failures are retried up to ``max_retries`` times
        (fresh key); an unconverged KE/KI escalates the restart budget
        and Chebyshev filter, then falls back to the direct TT variant;
        a mixed/fast refinement stalling above tolerance reruns at fp64.
      ``'ignore'`` — the pre-resilience behavior (no raises, no
        retries); the health verdict is still recorded.

    Every solve carries ``info['health']`` (per-stage verdicts, JSON-
    clean), ``info['recovery']`` (the rungs taken, possibly empty) and
    ``info['counters']`` (``core.instrument``'s host syncs, dispatches,
    lowered programs and KE runs on the split operator of the whole
    call, ints). The call is the profiler span ``repro.solve``, parent of
    every stage's span.
    """
    validate_on_failure(on_failure)
    kw: Dict[str, Any] = dict(
        variant=variant, which=which, invert=invert, gs2=gs2, gs1=gs1,
        td1=td1, band_width=band_width, block=block, m=m, tol=tol,
        max_restarts=max_restarts, use_kernel=use_kernel, key=key,
        mesh=mesh, clustered=clustered, machine=machine,
        krylov_block=krylov_block, filter=filter, precision=precision,
        refine=refine, refine_tol=refine_tol,
        refine_max_steps=refine_max_steps)
    with counting() as counters, span("solve", n=int(A.shape[0]), s=int(s),
                                      variant=variant):
        res = _contained(A, B, s, kw, on_failure, max_retries)
    res.info["counters"] = counters
    return res


def _contained(A, B, s: int, kw: Dict[str, Any], on_failure: str,
               max_retries: int) -> GSyEigResult:
    """``solve``'s body: the attempts and the degradation ladder."""
    recovery: list = []
    max_restarts, precision = kw["max_restarts"], kw["precision"]

    def attempt(attempt_kw):
        res = _solve_once(A, B, s, on_failure=on_failure,
                          recovery=recovery, **attempt_kw)
        stages = res.info.pop("_stage_health", {})
        # final output sentinel: host-side on the (s,)/(n, s) results the
        # caller fetches anyway — zero extra dispatches
        out_ok = host_finite(*fetch((res.evals, res.X)))
        stages["OUT"] = out_ok
        res.info["health"] = verdict_from_stages(stages).as_json_dict()
        res.info["recovery"] = recovery
        if not out_ok and on_failure != "ignore":
            raise SolverError(
                "solver produced non-finite eigenpairs", stage="OUT",
                reason="nonfinite_output",
                hint="every stage sentinel passed but the output is "
                     "corrupt — suspect the back-transform operands; "
                     "transient corruption is retryable under "
                     "on_failure='recover'", recovery=recovery,
                health=res.info["health"])
        return res

    retries = 0
    retry_rung = None
    while True:
        try:
            res = attempt(kw)
            break
        except SolverError as err:
            transient = err.diagnosis["reason"] in ("nonfinite_stage",
                                                    "nonfinite_output")
            if not (on_failure == "recover" and transient
                    and retries < max_retries):
                raise
            retries += 1
            retry_rung = rung("transient_retry", err.diagnosis["stage"],
                              "attempt", attempt=retries)
            recovery.append(retry_rung)
            base_key = (kw["key"] if kw["key"] is not None
                        else jax.random.PRNGKey(20120520))
            kw = dict(kw, key=jax.random.fold_in(base_key, 1000 + retries))
    if retry_rung is not None:
        retry_rung["outcome"] = "recovered"

    # --- ladder: unconverged Krylov -> escalate -> TT fallback -----------
    if on_failure == "recover" and not res.info.get("converged", True):
        resolved = res.info["variant"]
        fd = int(res.info.get("krylov", {}).get("filter_degree", 0))
        esc_restarts = int(max_restarts) * 4
        esc_filter = max(16, fd)
        r = rung("escalate_krylov", f"{resolved}_iter", "attempt",
                 max_restarts=esc_restarts, filter_degree=esc_filter)
        recovery.append(r)
        res2 = attempt(dict(kw, variant=resolved,
                            max_restarts=esc_restarts, filter=esc_filter))
        if res2.info.get("converged", True):
            r["outcome"] = "recovered"
            res = res2
        else:
            r["outcome"] = "failed"
            fb = rung("fallback_variant", f"{resolved}_iter", "attempt",
                      variant="TT")
            recovery.append(fb)
            res = attempt(dict(kw, variant="TT"))
            fb["outcome"] = ("recovered"
                             if res.info.get("converged", True) else "failed")

    # --- ladder: demoted refinement stalled above tol -> fp64 rerun ------
    rinfo = res.info.get("refinement")
    if (on_failure == "recover" and precision != "fp64" and rinfo
            and not rinfo.get("converged", True) and rinfo.get("stalled")):
        r = rung("escalate_precision", "RF", "attempt",
                 from_precision=precision, to_precision="fp64")
        recovery.append(r)
        res = attempt(dict(kw, variant=res.info["variant"],
                           precision="fp64", refine=True))
        r["outcome"] = ("recovered"
                        if res.info.get("refinement",
                                        {}).get("converged", True)
                        else "failed")
    return res
