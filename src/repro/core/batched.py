"""Batched GSYEIG: whole variant pipelines vmapped over stacked pencils.

The paper's two driver applications solve *sequences* of same-shape pencils
(one per MD timestep / DFT SCF iteration). Solving them one `solve` call at
a time leaves throughput on the table twice over: every stage pays its
dispatch latency per pencil, and the hardware never sees a batch dimension.
``solve_batched`` fixes both — each variant's full pipeline (GS1 -> GS2 ->
reduction -> tridiagonal eigensolver -> back-transforms) is compiled ONCE as
a single vmapped program over ``(batch, n, n)`` operand stacks.

Compiled pipelines are cached in a shape-bucket table keyed on
``(n, s, variant, which, ...)`` so a serving engine (see
``repro.serve.eigen_engine``) can stream requests through hot programs.

All four paper variants are supported:
  TD / TT — direct pipelines, every stage vmapped
  KE / KI — the fully jitted ``lanczos_solve_jit`` driver vmapped (fixed
            restart budget; per-pencil convergence flags are returned)
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .back_transform import back_transform_generalized
from .cholesky import cholesky_upper
from .lanczos import default_subspace, lanczos_solve_jit
from .operators import ExplicitC, ImplicitC
from .precision import (compute_dtype, default_refine_steps, ensure_strong,
                        exact_matmuls, validate_precision)
from .refinement import default_guard, refine_eigenpairs_fixed
from .residuals import b_normalize
from .sbr import apply_q2, band_chase, reduce_to_band
from .standard_form import to_standard_two_trsm
from .tridiag import apply_q, tridiagonalize
from .tridiag_eig import eigh_tridiag_selected

BATCHED_VARIANTS = ("TD", "TT", "KE", "KI")


class BatchedSolveResult(NamedTuple):
    evals: jax.Array       # (batch, s) ascending per pencil
    X: jax.Array           # (batch, n, s) B-orthonormal eigenvectors
    converged: jax.Array   # (batch,) bool (always True for TD/TT)
    healthy: jax.Array     # (batch,) bool fused finite-sentinel verdict
    info: Dict[str, Any]


# --------------------------------------------------------------------------
# per-pencil pipelines (vmapped below);
# signature: (A, B, key) -> (lam, X, ok, healthy)
# --------------------------------------------------------------------------


def _output_sentinel(lam, X):
    """Fused per-pencil health sentinel: two reductions folded into the
    ONE vmapped bucket program — zero extra dispatches (the static
    auditor pins ``max_dispatches`` of every ``solve_batched_*`` entry).
    A non-SPD B (NaN Cholesky) or a demoted-stage overflow propagates
    into (lam, X), so finiteness of the outputs covers every stage."""
    return jnp.isfinite(lam).all() & jnp.isfinite(X).all()

def _standard_form(A, B):
    U = cholesky_upper(B)
    C = to_standard_two_trsm(A, U)
    return U, C


def _finalize_invert(lam, X, B_orig):
    """Undo the inverse-pair trick per pencil (mirror of gsyeig._finalize)."""
    lam = 1.0 / lam
    order = jnp.argsort(lam)
    return lam[order], b_normalize(X[:, order], B_orig)


def _refine_fixed(lam, X, A0, B0, which0: str, refine_steps: int, key):
    """Fused fixed-step refinement against the ORIGINAL pencil (after the
    invert-undo, so `which0` is the caller's end)."""
    if refine_steps <= 0:
        return lam, X
    s, n = X.shape[1], X.shape[0]
    return refine_eigenpairs_fixed(A0, B0, lam, X, which=which0,
                                   steps=refine_steps,
                                   guard=default_guard(s, n),
                                   key=jax.random.fold_in(key, 7))


def _pipeline_direct(A, B, key, *, s: int, variant: str, which: str,
                     band_width: int, invert: bool, tt3: str = "batched",
                     cdtype=None, refine_steps: int = 0):
    A0, B0, which0 = A, B, which
    B_orig = B
    if invert:
        A, B = B, A
        which = "largest" if which == "smallest" else "smallest"
    n = A.shape[0]
    U, C = _standard_form(A, B)
    # mixed precision: the reduction + back-transform stages run in the
    # compute dtype; Cholesky/standard form (above) and the tridiagonal
    # eigensolve stay fp64, exactly as in gsyeig.solve
    Cw = C if cdtype is None else C.astype(cdtype)
    ks = jnp.arange(s) if which == "smallest" else jnp.arange(n - s, n)
    if variant == "TD":
        res = tridiagonalize(Cw)
        lam, Z = eigh_tridiag_selected(res.d.astype(jnp.float64),
                                       res.e.astype(jnp.float64),
                                       ks, key, method=tt3)
        Y = apply_q(res, Z if cdtype is None else Z.astype(cdtype))
    else:  # TT
        # the fused one-program panel sweep (kernels/house_panel + SYR2K
        # ladder) vmaps as-is: default_n_chunks sees the per-pencil n;
        # the TT3 stage (kernels/tridiag_eig) is likewise plain traceable
        # jnp, so the bucket's tridiagonal solves are part of this ONE
        # vmapped program — no per-pencil host dispatch anywhere
        band = reduce_to_band(Cw, w=band_width)
        chase = band_chase(band.Wb, band_width)
        lam, Z = eigh_tridiag_selected(chase.d.astype(jnp.float64),
                                       chase.e.astype(jnp.float64),
                                       ks, key, method=tt3)
        Zc = Z if cdtype is None else Z.astype(cdtype)
        Y = band.Q1 @ apply_q2(chase, Zc, band_width)
    Y = Y.astype(A.dtype)
    X = back_transform_generalized(U, Y)
    if invert:
        lam, X = _finalize_invert(lam, X, B_orig)
    lam, X = _refine_fixed(lam, X, A0, B0, which0, refine_steps, key)
    return lam, X, jnp.asarray(True), _output_sentinel(lam, X)


def _pipeline_krylov(A, B, key, *, s: int, variant: str, which: str,
                     m: int, max_restarts: int, invert: bool, p: int,
                     filter_degree: int, cdtype_name: str | None = None,
                     refine_steps: int = 0):
    A0, B0, which0 = A, B, which
    B_orig = B
    if invert:
        A, B = B, A
        which = "largest" if which == "smallest" else "smallest"
    U, C = _standard_form(A, B)
    op = ExplicitC(C) if variant == "KE" else ImplicitC(A, U)
    arp_which = "SA" if which == "smallest" else "LA"
    v0 = jax.random.normal(key, (A.shape[0], p), A.dtype)
    lam, Y, _, converged, healthy = lanczos_solve_jit(
        op, v0, s, m, which=arp_which, max_restarts=max_restarts, p=p,
        filter_degree=filter_degree, compute_dtype=cdtype_name)
    order = jnp.argsort(lam)
    lam, Y = lam[order], Y[:, order]
    X = back_transform_generalized(U, Y)
    if invert:
        lam, X = _finalize_invert(lam, X, B_orig)
    lam, X = _refine_fixed(lam, X, A0, B0, which0, refine_steps, key)
    return lam, X, converged, healthy & _output_sentinel(lam, X)


# --------------------------------------------------------------------------
# shape-bucketed pipeline cache
# --------------------------------------------------------------------------

# (n, s, variant, which, band_width, m, max_restarts, invert, p,
#  filter_degree, dtype, tt3) -> jitted
_PIPELINE_CACHE: Dict[Tuple, Any] = {}
# (pipeline_cache_key, batch) -> AOT-compiled executable; splitting the
# lower+compile step out of the dispatch is what lets ``solve_batched``
# report execution-only wall time (and an honest ``cache_hit`` flag)
_EXEC_CACHE: Dict[Tuple, Any] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def pipeline_cache_key(n: int, s: int, variant: str, which: str, *,
                       band_width: int = 8, m: int | None = None,
                       max_restarts: int = 200, invert: bool = False,
                       p: int = 1, filter_degree: int = 0,
                       dtype=jnp.float64, tt3: str = "batched",
                       precision: str = "fp64",
                       refine_steps: int | None = None) -> Tuple:
    if variant in ("KE", "KI") and m is None:
        m = default_subspace(s, n, p)
    if refine_steps is None:
        refine_steps = default_refine_steps(precision)
    return (int(n), int(s), variant, which, int(band_width),
            None if m is None else int(m), int(max_restarts), bool(invert),
            int(p), int(filter_degree), jnp.dtype(dtype).name, tt3,
            validate_precision(precision), int(refine_steps))


def get_pipeline(n: int, s: int, variant: str, which: str, *,
                 band_width: int = 8, m: int | None = None,
                 max_restarts: int = 200, invert: bool = False,
                 p: int = 1, filter_degree: int = 0,
                 dtype=jnp.float64, tt3: str = "batched",
                 precision: str = "fp64", refine_steps: int | None = None):
    """The jitted vmapped pipeline for one shape bucket (cached).

    ``p`` (Lanczos block size) and ``filter_degree`` (Chebyshev start-block
    filter) parameterize the Krylov pipelines; ``tt3`` selects the
    tridiagonal-stage method of the direct pipelines (see
    ``core.tridiag_eig.eigh_tridiag_selected``); ``precision`` /
    ``refine_steps`` select the compute dtype of the GEMM-heavy stages and
    the fused fp64 fixed-step refinement that buys the accuracy back (see
    ``core.precision`` / ``core.refinement``). All are compile-time
    choices, hence part of the bucket key."""
    assert variant in BATCHED_VARIANTS, variant
    ckey = pipeline_cache_key(n, s, variant, which, band_width=band_width,
                              m=m, max_restarts=max_restarts, invert=invert,
                              p=p, filter_degree=filter_degree, dtype=dtype,
                              tt3=tt3, precision=precision,
                              refine_steps=refine_steps)
    fn = _PIPELINE_CACHE.get(ckey)
    if fn is not None:
        _CACHE_STATS["hits"] += 1
        return fn, ckey
    _CACHE_STATS["misses"] += 1
    steps = ckey[-1]
    cdtype = None if precision == "fp64" else compute_dtype(precision)
    if variant in ("TD", "TT"):
        one = partial(_pipeline_direct, s=s, variant=variant, which=which,
                      band_width=band_width, invert=invert, tt3=tt3,
                      cdtype=cdtype, refine_steps=steps)
    else:
        m_eff = m if m is not None else default_subspace(s, n, p)
        one = partial(_pipeline_krylov, s=s, variant=variant, which=which,
                      m=m_eff, max_restarts=max_restarts, invert=invert,
                      p=p, filter_degree=filter_degree,
                      cdtype_name=None if cdtype is None
                      else jnp.dtype(cdtype).name,
                      refine_steps=steps)
    fn = jax.jit(jax.vmap(one))
    _PIPELINE_CACHE[ckey] = fn
    return fn, ckey


def cache_stats() -> Dict[str, int]:
    return dict(_CACHE_STATS, entries=len(_PIPELINE_CACHE),
                exec_entries=len(_EXEC_CACHE))


def clear_pipeline_cache() -> None:
    _PIPELINE_CACHE.clear()
    _EXEC_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0)


# --------------------------------------------------------------------------
# public driver
# --------------------------------------------------------------------------

@exact_matmuls
def solve_batched(
    A: jax.Array,
    B: jax.Array,
    s: int,
    variant: str = "TD",
    which: str = "smallest",
    invert: bool = False,
    band_width: int = 8,
    m: int | None = None,
    max_restarts: int = 200,
    key: jax.Array | None = None,
    p: int = 1,
    filter_degree: int = 0,
    tt3: str = "batched",
    precision: str = "fp64",
    refine_steps: int | None = None,
) -> BatchedSolveResult:
    """Solve a stack of same-shape pencils ``A[i] X = B[i] X Lambda``.

    ``A``, ``B``: (batch, n, n). Returns per-pencil ascending eigenvalues
    (batch, s) and B-orthonormal eigenvectors (batch, n, s). ``invert``
    applies the paper's MD inverse-pair trick per pencil (requires A SPD).
    ``p`` / ``filter_degree`` select the block size and Chebyshev filter of
    the Krylov pipelines (ignored by TD/TT); ``tt3`` the direct pipelines'
    tridiagonal-stage method.

    The program comes from two caches: the shape-bucket jit cache (one
    traced pipeline per ``(n, s, variant, which, ...)``) and an AOT
    executable cache per ``(bucket, batch)``. A miss pays XLA compilation
    ONCE, reported separately as ``info['compile_s']`` with
    ``info['cache_hit'] = False`` — ``wall_s`` / ``pencils_per_s`` are
    execution-only either way, so cold-bucket throughput numbers are real.
    ``info['n_unconverged']`` counts pencils whose Krylov driver retired
    at the restart budget (with an ``info['warnings']`` entry when any
    did); TD/TT pencils always converge.

    ``precision`` demotes the GEMM-heavy stages of every pencil to the
    compute dtype of ``core.precision`` and fuses ``refine_steps``
    (default: ``default_refine_steps(precision)``) fixed fp64 refinement
    sweeps against the original pencils into the same compiled program.
    """
    assert A.ndim == 3 and A.shape == B.shape, (A.shape, B.shape)
    validate_precision(precision)
    A = ensure_strong(A)
    B = ensure_strong(B)
    batch, n, _ = A.shape
    if key is None:
        key = jax.random.PRNGKey(20120520)
    keys = jax.random.split(key, batch)
    fn, ckey = get_pipeline(n, s, variant, which, band_width=band_width,
                            m=m, max_restarts=max_restarts, invert=invert,
                            p=p, filter_degree=filter_degree, dtype=A.dtype,
                            tt3=tt3, precision=precision,
                            refine_steps=refine_steps)
    exec_key = (ckey, int(batch))
    compiled = _EXEC_CACHE.get(exec_key)
    cache_hit = compiled is not None
    compile_s = 0.0
    if not cache_hit:
        t0 = time.perf_counter()
        compiled = fn.lower(A, B, keys).compile()
        compile_s = time.perf_counter() - t0
        _EXEC_CACHE[exec_key] = compiled
    t0 = time.perf_counter()
    lam, X, converged, healthy = compiled(A, B, keys)
    jax.block_until_ready(lam)
    wall = time.perf_counter() - t0
    n_unconverged, n_unhealthy = (int(x) for x in jax.device_get(
        (jnp.sum(~converged), jnp.sum(~healthy))))
    info = {"variant": variant, "n": int(n), "s": int(s),
            "batch": int(batch), "which": which, "invert": bool(invert),
            "precision": precision, "refine_steps": int(ckey[-1]),
            "cache_key": ckey, "cache_hit": cache_hit,
            "compile_s": compile_s, "wall_s": wall,
            "pencils_per_s": batch / max(wall, 1e-12),
            "n_unconverged": n_unconverged, "n_unhealthy": n_unhealthy}
    if n_unconverged:
        info["warnings"] = [
            f"{variant}: {n_unconverged}/{batch} pencils retired at the "
            f"restart budget (max_restarts={max_restarts}) without "
            f"converging; their residuals may exceed tolerance"]
    if n_unhealthy:
        info.setdefault("warnings", []).append(
            f"{variant}: {n_unhealthy}/{batch} pencils produced NON-FINITE "
            f"eigenpairs (non-SPD B or overflow in a demoted stage); see "
            f"result.healthy for the per-pencil verdicts")
    return BatchedSolveResult(evals=lam, X=X, converged=converged,
                              healthy=healthy, info=info)


__all__ = ["solve_batched", "BatchedSolveResult", "BATCHED_VARIANTS",
           "get_pipeline", "pipeline_cache_key", "cache_stats",
           "clear_pipeline_cache"]
