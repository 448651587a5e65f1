"""The repo's budget contracts: every solver entry point, registered.

This module is where the scattered PR-5/6/7 invariants live now — the
named constants below are imported by the tests that used to hard-code
them, and :func:`register_all` builds the :mod:`registry` entries the
``launch/audit.py`` CLI (and the ``assert_program_budget`` pytest fixture)
enforce. Everything is lowered on a small canonical spec
(:class:`AuditSpec`); the contracts are structural (collectives per step,
dispatch counts, loop shapes), so the small spec proves the same
invariants the production shapes rely on.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .registry import (AuditEntry, BudgetContract, DEFAULT_ALLOWED_DTYPES,
                       ProgramSpec, register)

# ------------------------------------------------------------------------
# published budget constants — the single source of truth the tests import
# ------------------------------------------------------------------------

#: fused stage-1 sweep (local or distributed): whole reduction in <= 3 host
#: dispatches (sweep program + band repack + slack for the pad path)
TT1_FUSED_MAX_DISPATCHES = 3
#: collectives ONE panel iteration of ``band_sweep_program`` executes:
#: all_gather(panel) + psum(coupling) + all_gather(Z)
TT1_COLLECTIVES_PER_PANEL = 3
#: the stepwise per-panel TT1 baseline pays at least this many dispatches
#: per panel (house_panel + coupling + update + Q1 accumulation)
TT1_STEPWISE_DISPATCHES_PER_PANEL = 4
#: communication-avoiding block Lanczos: collectives per p-column block
#: step of the fused matvec (one psum + one all_gather)
KE_COLLECTIVES_PER_BLOCK_STEP = 2
#: collectives appearing in the lowered ke_restart_program *text* (the
#: loop body is written once in StableHLO)
KE_HLO_ALL_REDUCE_MAX = 1
KE_HLO_ALL_GATHER_MAX = 1
#: all_gathers in the lowered tt3_program text: the lam gather + the
#: per-round Z gather (fori body appears once)
TT3_HLO_ALL_GATHER_MAX = 2
#: host dispatches the resilience health sentinels may ADD to any fused
#: program — pinned to 0: every stage-boundary ``is_finite`` verdict is
#: traced into an existing program (``resilience.health``), so the
#: dispatch budgets below hold UNCHANGED with sentinels active. The
#: auditor enforces both sides: ``min_isfinite_sites`` proves the
#: sentinel is present, this constant proves it is free.
SENTINEL_EXTRA_DISPATCHES = 0


#: dtypes the mixed-precision (fp32 compute) pipelines may mention on top
#: of the fp64 set: the demoted GEMM stages and the fp32 LU of the
#: refinement corrector
MIXED_ALLOWED_DTYPES: Tuple[str, ...] = DEFAULT_ALLOWED_DTYPES + ("float32",)
#: the fast (bf16 storage / fp32 accumulation) pipelines additionally
#: carry bfloat16 operands
FAST_ALLOWED_DTYPES: Tuple[str, ...] = MIXED_ALLOWED_DTYPES + ("bfloat16",)


def ke_dispatch_budget(n_restart: int) -> int:
    """Host dispatches of the fused distributed Krylov stage: one program
    per thick restart, plus prep (bounds probe / Chebyshev filter) and the
    final Ritz extraction."""
    return n_restart + 2


def lanczos_block_dispatch_budget(n_restart: int) -> int:
    """Host dispatches of the local fused-restart driver
    (``lanczos_solve``): segment+restart fused per restart, one extra
    final segment + one Ritz extraction."""
    return 2 * n_restart + 2


def lanczos_single_dispatch_budget(n_restart: int) -> int:
    """Host dispatches of the legacy per-stage local driver: segment,
    restart math and convergence check each restart, plus startup/finish."""
    return 3 * n_restart + 4


def tt3_dist_collectives(iters: int) -> int:
    """Static collective total of the spectrum-partitioned TT3: ONE lam
    all_gather + one Z all_gather per inverse-iteration round."""
    return 1 + iters


# ------------------------------------------------------------------------
# canonical audit spec
# ------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AuditSpec:
    """The shape bucket every contract is lowered on. Small on purpose —
    the contracts are structural, so tracing stays cheap in CI."""
    n: int = 64
    s: int = 4
    w: int = 8
    p: int = 4            # Lanczos block size
    m: int = 24           # Lanczos subspace
    kb: int = 12          # Chebyshev bound-probe steps
    filter_degree: int = 8
    tt3_iters: int = 3    # inverse-iteration rounds
    tt3_max_iters: int = 80
    batch: int = 2        # solve_batched bucket batch
    dtype_name: str = "float64"

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    def as_json_dict(self) -> dict:
        return dataclasses.asdict(self)


def _sds(*shape, dtype=jnp.float64):
    return jax.ShapeDtypeStruct(shape, dtype)


def make_mesh_2dev(shape: Tuple[int, int] = (2, 1)):
    """The audit mesh: data=2 so the row collectives are real, not no-ops.
    Requires >= 2 visible devices (``launch/audit.py`` forces host devices
    before importing jax, the ``launch/eigsolve.py`` idiom)."""
    from repro.dist.partitioning import make_mesh
    return make_mesh(shape, ("data", "model"))


# ------------------------------------------------------------------------
# entry builders
# ------------------------------------------------------------------------

def _build_reduce_to_band(spec: AuditSpec):
    from repro.core.sbr import _reduce_to_band_program, default_n_chunks
    n, w = spec.n, spec.w
    C = _sds(n, n, dtype=spec.dtype)
    return [ProgramSpec(
        name="_reduce_to_band_program", fn=_reduce_to_band_program,
        args=(C,), kwargs=dict(w=w, n_chunks=default_n_chunks(n, w)))]


def _build_band_chase(spec: AuditSpec):
    from repro.core.sbr import band_chase
    Wb = _sds(spec.w + 1, spec.n, dtype=spec.dtype)
    return [ProgramSpec(name="band_chase", fn=partial(band_chase, w=spec.w),
                        args=(Wb,))]


def _chase_shapes(spec: AuditSpec):
    from repro.core.sbr import band_chase
    Wb = _sds(spec.w + 1, spec.n, dtype=spec.dtype)
    return jax.eval_shape(partial(band_chase, w=spec.w), Wb)


def _build_apply_q2(spec: AuditSpec):
    from repro.core.sbr import apply_q2
    chase = _chase_shapes(spec)
    Z = _sds(spec.n, spec.s, dtype=spec.dtype)
    return [ProgramSpec(name="apply_q2", fn=partial(apply_q2, w=spec.w),
                        args=(chase, Z))]


def _build_tridiag_eig_batched(spec: AuditSpec):
    from repro.core.tridiag_eig import eigh_tridiag_selected
    n, s = spec.n, spec.s
    d = _sds(n, dtype=spec.dtype)
    e = _sds(n - 1, dtype=spec.dtype)
    ks = jnp.arange(s)
    key = jax.random.PRNGKey(0)

    def prog(d, e, ks, key):
        return eigh_tridiag_selected(d, e, ks, key, method="batched")

    return [ProgramSpec(name="tridiag_eig_batched", fn=prog,
                        args=(d, e, ks, key))]


def _build_lanczos_solve_jit(spec: AuditSpec):
    from repro.core.lanczos import lanczos_solve_jit
    from repro.core.operators import ExplicitC
    n, s, m, p = spec.n, spec.s, spec.m, spec.p
    C = _sds(n, n, dtype=spec.dtype)
    v0 = _sds(n, p, dtype=spec.dtype)

    def prog(C, v0):
        return lanczos_solve_jit(ExplicitC(C), v0, s, m, which="SA",
                                 max_restarts=8, p=p)

    return [ProgramSpec(name="lanczos_solve_jit", fn=prog, args=(C, v0),
                        with_hlo=False)]


def _build_solve_batched(spec: AuditSpec, variant: str,
                         precision: str = "fp64"):
    from repro.core.batched import get_pipeline
    n, s, batch = spec.n // 2, spec.s, spec.batch
    fn, _ = get_pipeline(n, s, variant, "smallest", band_width=4,
                         p=spec.p if variant in ("KE", "KI") else 1,
                         max_restarts=8, precision=precision)
    A = _sds(batch, n, n, dtype=spec.dtype)
    B = _sds(batch, n, n, dtype=spec.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), batch)
    suffix = "" if precision == "fp64" else f"_{precision}"
    return [ProgramSpec(name=f"solve_batched_{variant}{suffix}", fn=fn,
                        args=(A, B, keys), with_hlo=False)]


def _build_band_sweep(spec: AuditSpec, mesh):
    from repro.core.sbr import _jit_pack
    from repro.dist.sharded_la import band_sweep_program
    n, w = spec.n, spec.w
    prog = band_sweep_program(mesh, n, w, spec.dtype_name)
    M = _sds(n, n, dtype=spec.dtype)
    Q = _sds(n, n, dtype=spec.dtype)
    return [
        ProgramSpec(name="band_sweep_program", fn=prog, args=(M, Q)),
        ProgramSpec(name="_jit_pack", fn=_jit_pack, args=(M,),
                    kwargs=dict(w=w), with_hlo=False),
    ]


def _build_ke_restart(spec: AuditSpec, mesh):
    from repro.core.lanczos import restart_schedule
    from repro.dist.eigensolver import ke_restart_program
    n, s, p, m = spec.n, spec.s, spec.p, spec.m
    keep = restart_schedule(s, m, p)[0]
    prog = ke_restart_program(mesh, n, p, m, s, keep, "LA", spec.dtype_name)
    C = _sds(n, n, dtype=spec.dtype)
    V = _sds(n, m + p, dtype=spec.dtype)
    T = _sds(m + p, m + p, dtype=spec.dtype)
    j0 = jnp.asarray(0)
    tol = jnp.asarray(1e-9, spec.dtype)
    return [ProgramSpec(name="ke_restart_program", fn=prog,
                        args=(C, V, T, j0, tol))]


def _build_ke_prep(spec: AuditSpec, mesh):
    from repro.dist.eigensolver import ke_prep_program
    n, s, p = spec.n, spec.s, spec.p
    prog = ke_prep_program(mesh, n, p, spec.kb, spec.filter_degree, s,
                           "LA", spec.dtype_name)
    C = _sds(n, n, dtype=spec.dtype)
    X0 = _sds(n, p, dtype=spec.dtype)
    return [ProgramSpec(name="ke_prep_program", fn=prog, args=(C, X0))]


def _build_tt3(spec: AuditSpec, mesh):
    from repro.dist.eigensolver import tt3_program
    from repro.kernels.tridiag_eig.ops import SCAN_UNROLL
    n = spec.n
    s_pad = -(-spec.s // int(mesh.devices.size)) * int(mesh.devices.size)
    prog = tt3_program(mesh, n, s_pad, spec.tt3_max_iters, spec.tt3_iters,
                       SCAN_UNROLL, spec.dtype_name)
    d = _sds(n, dtype=spec.dtype)
    e = _sds(n - 1, dtype=spec.dtype)
    ks = jnp.arange(s_pad)
    X0 = _sds(n, s_pad, dtype=spec.dtype)
    return [ProgramSpec(name="tt3_program", fn=prog, args=(d, e, ks, X0))]


# kernel wrapper entries: (name, builder) — each forces the Pallas path
# off-TPU (interpret mode) so the lowered jaxpr contains the real
# pallas_call with its GridMapping for the kernel lint

def _build_stage_sentinels(spec: AuditSpec):
    """The standalone fused stage programs of ``gsyeig``: Cholesky + its
    health verdict (GS1) and the TRSM congruence + its finiteness verdict
    (GS2) each lower to ONE program whose sentinel is part of the trace."""
    from repro.core.gsyeig import _jit_chol, _jit_gs2_trsm
    n = spec.n
    B = _sds(n, n, dtype=spec.dtype)
    A = _sds(n, n, dtype=spec.dtype)
    U = _sds(n, n, dtype=spec.dtype)
    return [
        ProgramSpec(name="gs1_chol_sentinel", fn=_jit_chol, args=(B,),
                    with_hlo=False),
        ProgramSpec(name="gs2_trsm_sentinel", fn=_jit_gs2_trsm,
                    args=(A, U), with_hlo=False),
    ]


def _build_kernel_gemm(spec: AuditSpec):
    from repro.kernels.gemm.ops import gemm
    A = _sds(96, 64, dtype=spec.dtype)
    B = _sds(64, 96, dtype=spec.dtype)
    return [ProgramSpec(name="gemm", fn=gemm, args=(A, B),
                        kwargs=dict(force_interpret=True), with_hlo=False)]


def _build_kernel_symv(spec: AuditSpec):
    from repro.kernels.symv.ops import symv
    n = spec.n
    return [ProgramSpec(name="symv", fn=symv,
                        args=(_sds(n, n, dtype=spec.dtype),
                              _sds(n, dtype=spec.dtype)),
                        kwargs=dict(force_interpret=True), with_hlo=False)]


def _build_kernel_syr2k(spec: AuditSpec):
    from repro.kernels.syr2k.ops import syr2k
    n, k = spec.n, spec.w
    return [ProgramSpec(name="syr2k", fn=syr2k,
                        args=(_sds(n, n, dtype=spec.dtype),
                              _sds(n, k, dtype=spec.dtype),
                              _sds(n, k, dtype=spec.dtype)),
                        kwargs=dict(force_interpret=True), with_hlo=False)]


def _build_kernel_trsm(spec: AuditSpec):
    from repro.kernels.trsm.ops import trsm
    n, s = spec.n, spec.s
    return [ProgramSpec(name="trsm", fn=trsm,
                        args=(_sds(n, n, dtype=spec.dtype),
                              _sds(n, s, dtype=spec.dtype)),
                        kwargs=dict(force_interpret=True), with_hlo=False)]


def _build_kernel_band_mv(spec: AuditSpec):
    from repro.kernels.band_mv.ops import band_mv
    n, w = spec.n, spec.w

    def prog(band, x):
        return band_mv(band, x, w=w, force_interpret=True)

    return [ProgramSpec(name="band_mv", fn=prog,
                        args=(_sds(n, w + 1, dtype=spec.dtype),
                              _sds(n, dtype=spec.dtype)), with_hlo=False)]


def _build_kernel_rot_apply(spec: AuditSpec):
    from repro.kernels.rot_apply.ops import rot_apply
    G, L = 8, spec.n

    def prog(pairs, cs):
        return rot_apply(pairs, cs, force_kernel=True, force_interpret=True)

    return [ProgramSpec(name="rot_apply", fn=prog,
                        args=(_sds(G, 2, L, dtype=spec.dtype),
                              _sds(G, 2, dtype=spec.dtype)), with_hlo=False)]


def _build_kernel_house_panel(spec: AuditSpec):
    from repro.kernels.house_panel.ops import house_panel
    n, w = spec.n, spec.w

    def prog(E):
        return house_panel(E, w, force_kernel=True, force_interpret=True)

    return [ProgramSpec(name="house_panel", fn=prog,
                        args=(_sds(n, w, dtype=spec.dtype),),
                        with_hlo=False)]


def _build_kernel_tridiag_eig(spec: AuditSpec):
    from repro.kernels.tridiag_eig.ops import bisect_sturm
    n, s = spec.n, spec.s

    def prog(d, e):
        return bisect_sturm(d, e, jnp.arange(s), force_kernel=True,
                            force_interpret=True)

    return [ProgramSpec(name="bisect_sturm", fn=prog,
                        args=(_sds(n, dtype=spec.dtype),
                              _sds(n - 1, dtype=spec.dtype)),
                        with_hlo=False)]


# ------------------------------------------------------------------------
# registration
# ------------------------------------------------------------------------

def _n_panels(n: int, w: int) -> int:
    from repro.core.sbr import _n_panels as f
    return f(n, w)


_NO_COMM = dict(exact_collectives=0, max_dynamic_whiles=0)


def register_all(spec: Optional[AuditSpec] = None,
                 mesh=None) -> AuditSpec:
    """Populate the registry for ``spec`` (idempotent: re-registering
    replaces). ``mesh=None`` still registers the mesh entries; they are
    skipped at check time when fewer than 2 devices are visible."""
    spec = spec or AuditSpec()

    def _mesh():
        return mesh if mesh is not None else make_mesh_2dev()

    register(AuditEntry(
        name="core/reduce_to_band",
        build=partial(_build_reduce_to_band, spec),
        contract=BudgetContract(
            max_dispatches=TT1_FUSED_MAX_DISPATCHES, **_NO_COMM,
            notes="local fused TT1: whole window ladder is ONE program"),
        tags=("core", "quick")))

    register(AuditEntry(
        name="core/band_chase",
        build=partial(_build_band_chase, spec),
        contract=BudgetContract(
            max_dispatches=1, **_NO_COMM,
            notes="TT2 wavefront chase: one program, static fori ladder"),
        tags=("core", "quick")))

    register(AuditEntry(
        name="core/apply_q2",
        build=partial(_build_apply_q2, spec),
        contract=BudgetContract(
            max_dispatches=1, **_NO_COMM,
            notes="TT4 rotation replay onto the (n, s) Ritz slab"),
        tags=("core", "quick")))

    register(AuditEntry(
        name="core/tridiag_eig_batched",
        build=partial(_build_tridiag_eig_batched, spec),
        contract=BudgetContract(
            max_dispatches=1, **_NO_COMM,
            notes="TT3/TD2 fused bisection + inverse iteration"),
        tags=("core", "quick")))

    register(AuditEntry(
        name="core/lanczos_solve_jit",
        build=partial(_build_lanczos_solve_jit, spec),
        contract=BudgetContract(
            max_dispatches=1, exact_collectives=0, max_dynamic_whiles=1,
            min_isfinite_sites=1,
            sentinel_extra_dispatches=SENTINEL_EXTRA_DISPATCHES,
            notes="fully jitted Krylov driver: ONE dynamic restart while; "
                  "the restart-health sentinel is fused into it"),
        tags=("core", "quick")))

    for variant in ("TD", "TT", "KE", "KI"):
        register(AuditEntry(
            name=f"serve/solve_batched_{variant}",
            build=partial(_build_solve_batched, spec, variant),
            contract=BudgetContract(
                max_dispatches=1, exact_collectives=0,
                max_dynamic_whiles=0 if variant in ("TD", "TT") else 1,
                min_isfinite_sites=1,
                sentinel_extra_dispatches=SENTINEL_EXTRA_DISPATCHES,
                notes="one vmapped program per shape bucket (per-pencil "
                      "output sentinel fused in)"),
            tags=("serve", "quick")))

    # mixed/fast precision policies: the same bucketed pipelines with the
    # GEMM stages demoted + fused fp64 refinement. The contract DECLARES
    # the policy's downcast edges (core.precision.declared_downcasts) and
    # widens the dtype set; any demotion outside the declaration is still
    # a leak, and the budget shape must not change with precision.
    from repro.core.precision import declared_downcasts
    precision_allowed = {"mixed": MIXED_ALLOWED_DTYPES,
                         "fast": FAST_ALLOWED_DTYPES}
    for variant, precision in (("TD", "mixed"), ("TT", "mixed"),
                               ("KE", "mixed"), ("KI", "mixed"),
                               ("TT", "fast"), ("KE", "fast")):
        register(AuditEntry(
            name=f"serve/solve_batched_{variant}_{precision}",
            build=partial(_build_solve_batched, spec, variant, precision),
            contract=BudgetContract(
                max_dispatches=1, exact_collectives=0,
                max_dynamic_whiles=0 if variant in ("TD", "TT") else 1,
                min_isfinite_sites=1,
                sentinel_extra_dispatches=SENTINEL_EXTRA_DISPATCHES,
                allowed_dtypes=precision_allowed[precision],
                declared_downcasts=declared_downcasts(precision),
                notes=f"{precision} pipeline: declared GEMM-stage "
                      "demotions + fused fp64 refinement, same budget "
                      "shape as the fp64 bucket"),
            tags=("serve", "precision", "quick")))

    register(AuditEntry(
        name="dist/band_sweep_program",
        build=lambda: _build_band_sweep(spec, _mesh()),
        contract=BudgetContract(
            max_dispatches=TT1_FUSED_MAX_DISPATCHES,
            max_collectives_per_step=TT1_COLLECTIVES_PER_PANEL,
            exact_collectives=TT1_COLLECTIVES_PER_PANEL
                * _n_panels(spec.n, spec.w),
            max_dynamic_whiles=0,
            notes="dist TT1: gather(panel) + psum(coupling) + gather(Z) "
                  "per panel, all inside ONE fori_loop program"),
        needs_mesh=True, tags=("dist", "quick")))

    register(AuditEntry(
        name="dist/ke_restart_program",
        build=lambda: _build_ke_restart(spec, _mesh()),
        contract=BudgetContract(
            max_dispatches=1,
            max_collectives_per_step=KE_COLLECTIVES_PER_BLOCK_STEP,
            exact_collectives=KE_COLLECTIVES_PER_BLOCK_STEP
                * (spec.m // spec.p),
            max_dynamic_whiles=0,
            min_isfinite_sites=1,
            sentinel_extra_dispatches=SENTINEL_EXTRA_DISPATCHES,
            notes="ONE dispatch per thick restart; psum + all_gather per "
                  "p-column block step of the fused matvec; the restart "
                  "health verdict rides in the same program"),
        needs_mesh=True, tags=("dist", "quick")))

    register(AuditEntry(
        name="dist/ke_prep_program",
        build=lambda: _build_ke_prep(spec, _mesh()),
        contract=BudgetContract(
            max_dispatches=1,
            max_collectives_per_step=KE_COLLECTIVES_PER_BLOCK_STEP,
            max_collectives=KE_COLLECTIVES_PER_BLOCK_STEP
                * (spec.kb + spec.filter_degree + 2),
            max_dynamic_whiles=0,
            notes="bounds probe + Chebyshev filter, fused matvec budget"),
        needs_mesh=True, tags=("dist", "quick")))

    register(AuditEntry(
        name="dist/tt3_program",
        build=lambda: _build_tt3(spec, _mesh()),
        contract=BudgetContract(
            max_dispatches=1,
            max_collectives_per_step=1,
            exact_collectives=tt3_dist_collectives(spec.tt3_iters),
            max_dynamic_whiles=0,
            notes="spectrum-partitioned TT3: 1 lam all_gather + one Z "
                  "all_gather per inverse-iteration round"),
        needs_mesh=True, tags=("dist", "quick")))

    register(AuditEntry(
        name="resilience/stage_sentinels",
        build=partial(_build_stage_sentinels, spec),
        contract=BudgetContract(
            max_dispatches=2, exact_collectives=0, max_dynamic_whiles=0,
            min_isfinite_sites=2,
            sentinel_extra_dispatches=SENTINEL_EXTRA_DISPATCHES,
            notes="GS1 Cholesky + GS2 TRSM with their health verdicts "
                  "fused in: the stage programs gsyeig.solve dispatches "
                  "anyway, so the sentinels are dispatch-free"),
        tags=("resilience", "quick")))

    kernel_builders = {
        "gemm": _build_kernel_gemm, "symv": _build_kernel_symv,
        "syr2k": _build_kernel_syr2k, "trsm": _build_kernel_trsm,
        "band_mv": _build_kernel_band_mv,
        "rot_apply": _build_kernel_rot_apply,
        "house_panel": _build_kernel_house_panel,
        "tridiag_eig": _build_kernel_tridiag_eig,
    }
    for kname, builder in kernel_builders.items():
        register(AuditEntry(
            name=f"kernels/{kname}",
            build=partial(builder, spec),
            contract=BudgetContract(
                max_dispatches=1, exact_collectives=0,
                max_dynamic_whiles=0, min_pallas_calls=1,
                notes="wrapper pads to tile multiples and launches the "
                      "Pallas kernel (interpret mode off-TPU)"),
            tags=("kernels", "quick")))

    return spec


__all__ = [
    "AuditSpec", "register_all", "make_mesh_2dev",
    "MIXED_ALLOWED_DTYPES", "FAST_ALLOWED_DTYPES",
    "TT1_FUSED_MAX_DISPATCHES", "TT1_COLLECTIVES_PER_PANEL",
    "TT1_STEPWISE_DISPATCHES_PER_PANEL", "KE_COLLECTIVES_PER_BLOCK_STEP",
    "KE_HLO_ALL_REDUCE_MAX", "KE_HLO_ALL_GATHER_MAX",
    "TT3_HLO_ALL_GATHER_MAX", "SENTINEL_EXTRA_DISPATCHES",
    "ke_dispatch_budget",
    "lanczos_block_dispatch_budget", "lanczos_single_dispatch_budget",
    "tt3_dist_collectives",
]
