"""KE/KI — implicitly-restarted BLOCK Lanczos (ARPACK DSAUPD/DSEUPD analogue).

We implement the symmetric thick-restart formulation (Wu & Simon, TRLan)
generalized to a *block / s-step* method: the factorization advances by a
whole (n, p) block per step — ONE fused multi-RHS matvec (a GEMM /
``kernels/symv.symm_block`` instead of p SYMVs), two-pass block
re-orthogonalization, and a QR of the residual block. For ``p == 1`` this
reduces exactly to the classical single-vector method (same shapes, same
restart schedule). The block structure is what makes the distributed KE
pipeline communication-avoiding: per block step the mesh pays ONE psum
(the matvec coupling) plus ONE all_gather (which doubles as the broadcast
because every shard runs the O(n m p) orthogonalization math redundantly —
the same trick ``sharded_la.band_sweep_program`` uses for panel QR),
instead of one collective round trip per matvec (see
``repro.dist.eigensolver.ke_restart_program``).

State maps onto fixed-shape JAX buffers: a single (n, m+p) basis buffer, a
dense (m+p, m+p) projected matrix, and restart = eigh of an m x m block.
Full (two-pass) re-orthogonalization is used, the O(nm)-per-iteration
worst case the paper quotes.

Two drivers:
  * ``lanczos_solve``      — host-driven restart loop (data-dependent
    iteration counts, per-stage timing for the benchmark tables). The
    whole-segment extension runs as ONE jitted program and the
    convergence test is a single-scalar ``jax.device_get``, so each
    restart costs O(1) device dispatches. The module counts host->device
    dispatches (``dispatch_count``) so the regression test can pin this.
  * ``lanczos_solve_jit``  — single jitted lax.while_loop (fixed
    max_restarts) used by the batched/dry-run path.

Both drivers support Chebyshev polynomial filtering of the starting block
(``filter_degree > 0``): spectral bounds come from a cheap k-step probe
(``core.filtering``) and the filter damps the unwanted end so clustered
DFT-like spectra converge inside the restart budget.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .instrument import DispatchCounter, fetch, span
from .looped import eigh_small, orthonormalize, qr_posdiag
from .operators import (OPERATORS, ExplicitC, ImplicitC, Operator,
                        apply_op, op_dim, op_dtype)


class LanczosResult(NamedTuple):
    evals: jax.Array        # (s,)
    evecs: jax.Array        # (n, s) Ritz vectors (orthonormal)
    n_matvec: int           # operator applications
    n_restart: int
    converged: bool
    resid_bounds: jax.Array  # (s,) ||B_q S[m-p:m, i]|| at exit
    healthy: bool = True     # fused finite-sentinel verdict at exit


# ---------------------------------------------------------------------------
# one block step + the jitted whole-segment program
# ---------------------------------------------------------------------------

def _block_step_impl(matvec, V: jax.Array, T: jax.Array, j: jax.Array,
                     p: int):
    """Extend the factorization by one (n, p) block: columns
    [j*p, (j+1)*p) of V (n, m+p), T ((m+p, m+p)).

    ``matvec`` is any traceable Y = C X closure taking an (n, p) block —
    ``apply_op`` on the local Operator pytrees (multi-RHS), or the fused
    psum+all_gather matvec inside a ``shard_map`` region (see
    ``repro.dist.eigensolver``). One call = p operator applications."""
    n, mpp = V.shape
    c0 = j * p
    Vj = jax.lax.dynamic_slice(V, (jnp.zeros((), c0.dtype), c0), (n, p))
    W = matvec(Vj)
    cols = jnp.arange(mpp)
    mask = (cols < c0 + p).astype(V.dtype)[:, None]
    # two-pass full block re-orthogonalization (Kahan twice-is-enough)
    H1 = (V.T @ W) * mask
    W = W - V @ H1
    H2 = (V.T @ W) * mask
    W = W - V @ H2
    H = H1 + H2                              # (m+p, p) projection coeffs
    Q, B = qr_posdiag(W)                     # residual block QR
    # block column of T: H on rows < (j+1)p, the new coupling B below
    Hb = H + jax.lax.dynamic_update_slice(
        jnp.zeros_like(H), B, (c0 + p, jnp.zeros((), c0.dtype)))
    T = jax.lax.dynamic_update_slice(T, Hb, (jnp.zeros((), c0.dtype), c0))
    T = jax.lax.dynamic_update_slice(T, Hb.T, (c0, jnp.zeros((), c0.dtype)))
    V = jax.lax.dynamic_update_slice(V, Q, (jnp.zeros((), c0.dtype), c0 + p))
    return V, T, B


def _segment_impl(matvec, V: jax.Array, T: jax.Array, j0, p: int = 1):
    """Block steps j0..q-1 as ONE lax.fori_loop — one dispatch per restart.

    ``j0`` is a traced BLOCK index (0 on the first sweep, ``keep // p``
    after a thick restart), so a single compilation serves the whole
    solve. Returns ``(V, T, B_q)`` with B_q the last (p, p) coupling."""
    n, mpp = V.shape
    q = (mpp - p) // p

    def body(j, carry):
        def run(args):
            V, T, _ = args
            return _block_step_impl(matvec, V, T, j, p)

        return jax.lax.cond(j >= j0, run, lambda a: a, carry)

    return jax.lax.fori_loop(0, q, body,
                             (V, T, jnp.zeros((p, p), V.dtype)))


@partial(jax.jit, static_argnames=("use_kernel", "p", "compute_dtype"),
         donate_argnums=(1, 2))
def _lanczos_segment(op: Operator, V: jax.Array, T: jax.Array, j0,
                     use_kernel: bool = False, p: int = 1,
                     compute_dtype: str | None = None):
    """Operator-pytree segment: op rides along as a traced argument so one
    compilation serves every problem of the same shape. ``compute_dtype``
    (a dtype NAME, static) demotes ONLY the operator application — the
    orthogonalization stays in V's dtype — without leaving this shared
    jit cache (a per-solve jit of a demoting closure would recompile the
    segment on every ``lanczos_solve`` call)."""
    if compute_dtype is not None:
        cdtype = jnp.dtype(compute_dtype)
        op_c = jax.tree_util.tree_map(lambda a: a.astype(cdtype), op)
        mv = lambda X: apply_op(op_c, X.astype(cdtype),  # noqa: E731
                                use_kernel=use_kernel).astype(V.dtype)
    else:
        mv = lambda X: apply_op(op, X, use_kernel=use_kernel)  # noqa: E731
    return _segment_impl(mv, V, T, j0, p)


def _make_segment(op, use_kernel: bool, p: int,
                  compute_dtype: str | None = None):
    """Segment driver for either op flavor.

    Operator pytrees reuse the module-level jitted segment (compile cache
    shared across solves), including the demoted-matvec case via the
    static ``compute_dtype`` name; bare matvec callables — e.g. a
    distributed closure — get a per-solve jit (the closure is stable
    across the restart loop, so each solve compiles the segment once)."""
    if isinstance(op, OPERATORS):
        return lambda V, T, j0: _lanczos_segment(
            op, V, T, j0, use_kernel=use_kernel, p=p,
            compute_dtype=compute_dtype)
    if callable(op):
        jit_seg = jax.jit(partial(_segment_impl, op, p=p),
                          donate_argnums=(0, 1))
        return lambda V, T, j0: jit_seg(V, T, j0)
    raise TypeError(f"op must be an Operator or a matvec callable: {op!r}")


@partial(jax.jit, static_argnames=("s", "keep", "m", "p", "which"))
def _restart_math(V: jax.Array, T: jax.Array, B_q: jax.Array,
                  tol_eff: jax.Array, s: int, keep: int, m: int, p: int,
                  which: str, resid_floor_rel: float = 0.0):
    """eigh of T_m, Ritz selection, residual bounds, thick-restart state AND
    the convergence verdict — everything per-restart in one jitted program,
    so the host only fetches one scalar (``all_conv``) to decide.

    Residual bound of Ritz pair i is ``||B_q S[m-p:m, i]||`` (the block
    generalization of |beta_m S[m-1, i]|); the thick restart keeps the
    leading ``keep`` Ritz vectors (keep is a multiple of p) plus the
    (n, p) residual block, with the (p, keep) coupling
    ``B_q S[m-p:m, :keep]`` in the arrowhead of the new T.

    ``resid_floor_rel`` is the mixed-precision escape hatch: a demoted
    matvec floors the attainable residual at ~eps_compute * ||C|| (not
    eps * |theta_i|), so the criterion also accepts bounds under
    ``resid_floor_rel * max|theta|`` — fp64 refinement recovers the rest."""
    Tm = 0.5 * (T[:m, :m] + T[:m, :m].T)
    theta, S = eigh_small(Tm)  # ascending
    if which == "LA":  # want the largest: reorder descending so wanted = first
        theta = theta[::-1]
        S = S[:, ::-1]
    b = B_q @ S[m - p:m, :]                 # (p, m) residual couplings
    resid = jnp.linalg.norm(b, axis=0)      # Ritz residual bounds, all m
    # ARPACK dsconv criterion: bound_i <= tol * max(eps^{2/3}, |theta_i|)
    eps = jnp.finfo(V.dtype).eps
    eps23 = eps ** (2.0 / 3.0)
    thresh = tol_eff * jnp.maximum(jnp.abs(theta[:s]), eps23)
    thresh = jnp.maximum(thresh, resid_floor_rel * jnp.max(jnp.abs(theta)))
    conv = resid[:s] <= thresh
    all_conv = jnp.all(conv)
    # fused health sentinel (zero extra dispatches — it rides out with
    # the verdict the host fetches anyway): a non-finite basis or T
    # propagates into theta/resid, so this catches NaN/inf anywhere in
    # the restart's state
    healthy = jnp.isfinite(theta).all() & jnp.isfinite(resid).all()
    # thick restart: keep leading `keep` Ritz pairs + the residual block
    V_new_cols = V[:, :m] @ S[:, :keep]                     # (n, keep)
    V_res = V[:, m:m + p]                                   # residual block
    V_restart = jnp.zeros_like(V)
    V_restart = V_restart.at[:, :keep].set(V_new_cols)
    V_restart = V_restart.at[:, keep:keep + p].set(V_res)
    T_new = jnp.zeros_like(T)
    T_new = T_new.at[jnp.arange(keep), jnp.arange(keep)].set(theta[:keep])
    T_new = T_new.at[keep:keep + p, :keep].set(b[:, :keep])
    T_new = T_new.at[:keep, keep:keep + p].set(b[:, :keep].T)
    return theta, S, resid, V_restart, T_new, all_conv, healthy


# dispatch accounting (observability + the regression test's hook)
_dispatch = DispatchCounter()

#: host->device dispatches issued by ``lanczos_solve`` since the last
#: ``reset_dispatch_count()`` (each jitted-program invocation counts 1)
dispatch_count = _dispatch.count
reset_dispatch_count = _dispatch.reset


def default_subspace(s: int, n: int, p: int = 1) -> int:
    """ARPACK-style default NCV: m in [2s, n), at least 20 — rounded up to
    a multiple of the block size p (and down so the (n, m+p) basis fits).

    For blocks the subspace additionally scales with p: the Krylov
    polynomial degree reachable per sweep is m/p, so keeping m fixed while
    raising p would trade convergence for communication 1:1. m ~ 10p keeps
    ~10 block steps per sweep (the single-vector default's depth at p=1)."""
    m = int(min(max(2 * s + 1, 20), n - 1))
    if p > 1:
        m = max(m, min(10 * p, n // 2))
        m = -(-m // p) * p                  # round up to a block multiple
        m = min(m, ((n - p) // p) * p)      # basis must fit: m + p <= n
    return m


def restart_schedule(s: int, m: int, p: int = 1) -> tuple:
    """(keep, per_restart) of the thick-restart drivers below: each restart
    keeps ``keep`` Ritz pairs (a multiple of the block size p, so restarts
    stay block-aligned) and extends by ``per_restart = m - keep`` matvecs
    (``per_restart // p`` block steps). The single source of truth — the
    cost model's dispatch/collective/restart estimates
    (``analysis.variant_model``) derive from it too."""
    keep = min(s + max((m - s) // 2, 1), m - 2)
    if p > 1:
        keep = min(-(-keep // p) * p, m - p)
    return keep, max(m - keep, 1)


def _seed_block(v0, n: int, p: int, key, dtype):
    """(n, p) starting block: v0 (or a random vector) in column 0, random
    fill for the rest; orthonormalized by the caller (QR / filter+QR)."""
    if v0 is None:
        return jax.random.normal(key, (n, p), dtype)
    v0 = jnp.asarray(v0, dtype)
    if v0.ndim == 1:
        if p == 1:
            return v0[:, None]
        rest = jax.random.normal(jax.random.fold_in(key, 1), (n, p - 1),
                                 dtype)
        return jnp.concatenate([v0[:, None], rest], axis=1)
    assert v0.shape == (n, p), (v0.shape, n, p)
    return v0


def lanczos_solve(op, s: int, which: str = "SA", m: int | None = None,
                  tol: float = 0.0, max_restarts: int = 500,
                  key: jax.Array | None = None, use_kernel: bool = False,
                  v0: jax.Array | None = None,
                  callback=None, n: int | None = None, p: int = 1,
                  filter_degree: int = 0,
                  compute_dtype=None) -> LanczosResult:
    """Host-driven thick-restart block Lanczos for s extremal eigenpairs.

    `op` is an Operator pytree (``operators.OPERATORS``) or any traceable
    block-matvec callable X -> C X on (n, p) blocks (for ``p == 1`` a
    plain ``lambda v: C @ v`` works on the (n, 1) column). For callables,
    the problem dimension comes from `v0` (or the explicit `n`).
    which: 'SA' (smallest algebraic) or 'LA' (largest algebraic).
    tol=0.0 reproduces ARPACK's default (machine precision criterion).
    ``p`` is the block / s-step size: each segment step advances p basis
    vectors with ONE fused multi-RHS matvec. ``filter_degree > 0``
    Chebyshev-filters the starting block (degree-d polynomial damping the
    unwanted end; bounds from a k-step probe — see ``core.filtering``),
    which is what makes clustered spectra converge inside the budget.
    `callback(k_restart, V, T, m)` enables checkpoint hooks (see dist/).

    ``compute_dtype`` (a dtype, or None = off) demotes ONLY the operator
    application — the basis, T and all restart/convergence math stay in
    the working dtype, and the convergence criterion is floored at the
    demoted matvec's attainable residual (``core.refinement`` recovers
    full accuracy afterwards).

    Per restart the host issues O(1) device dispatches: one jitted
    whole-segment program, one ``_restart_math``, and a single-scalar
    ``jax.device_get`` for the convergence verdict. Each is a span of
    ``core.instrument``: ``repro.ke.segment``, ``repro.ke.restart`` and
    ``repro.ke.sync`` (``restart=k``); the start block is
    ``repro.ke.prep``, the Ritz vectors after the loop ``repro.ke.ritz``.
    """
    if isinstance(op, OPERATORS):
        n = op_dim(op)
        dtype = op_dtype(op)
        matvec = lambda X: apply_op(op, X, use_kernel=use_kernel)  # noqa: E731
    else:
        if n is None:
            if v0 is None:
                raise ValueError("callable op needs `v0` or `n`")
            n = v0.shape[0]
        dtype = v0.dtype if v0 is not None else jnp.float64
        matvec = op
    resid_floor_rel = 0.0
    seg_cdtype = None
    cdtype = None if compute_dtype is None else jnp.dtype(compute_dtype)
    if cdtype is not None and cdtype != jnp.dtype(dtype):
        if isinstance(op, (ExplicitC, ImplicitC)):
            # op stays a pytree: the module-level jitted segment demotes
            # internally (static compute_dtype name), so the compile
            # cache keeps being shared across solves. matvec (used by the
            # filter / bound probes) demotes the same way.
            op_c = jax.tree_util.tree_map(lambda a: a.astype(cdtype), op)
            mv0 = lambda X: apply_op(op_c, X.astype(cdtype),  # noqa: E731
                                     use_kernel=use_kernel)
            seg_cdtype = jnp.dtype(cdtype).name
        else:
            base = matvec
            mv0 = lambda X: base(X.astype(cdtype))  # noqa: E731
        matvec = lambda X: mv0(X).astype(dtype)  # noqa: E731
        if seg_cdtype is None:
            op = matvec      # callable op: per-solve jit as before
        resid_floor_rel = 8.0 * float(jnp.finfo(cdtype).eps)
    if m is None:
        m = default_subspace(s, n, p)
    assert m % p == 0 and m + p <= n + (1 if p == 1 else 0), (m, p, n)
    assert 2 * s < m + 1, (s, m)
    keep, _ = restart_schedule(s, m, p)
    segment = _make_segment(op, use_kernel, p, compute_dtype=seg_cdtype)
    eps = float(jnp.finfo(dtype).eps)
    tol_eff = tol if tol > 0.0 else eps

    if key is None:
        key = jax.random.PRNGKey(272727)
    n_matvec = 0
    with span("ke.prep"):
        X0 = _seed_block(v0, n, p, key, dtype)
        if filter_degree > 0:
            from .filtering import (chebyshev_filter_jit,
                                    estimate_bounds_jit, filter_interval,
                                    probe_steps)
            kb = probe_steps(s, n)
            theta_p, beta_k = _dispatch(estimate_bounds_jit, matvec,
                                        jax.random.normal(
                                            jax.random.fold_in(key, 2),
                                            (n,), dtype), kb)
            a, b, a0 = filter_interval(theta_p, beta_k, s, which)
            X0 = _dispatch(chebyshev_filter_jit, matvec, X0, filter_degree,
                           a, b, a0)
            n_matvec += kb + filter_degree * p
        V = jnp.zeros((n, m + p), dtype)
        T = jnp.zeros((m + p, m + p), dtype)
        Q0, _ = qr_posdiag(X0)
        V = V.at[:, :p].set(Q0)

    j0 = 0
    theta = S = resid = None
    n_restart, converged, health_ok = max_restarts, False, True
    for k_restart in range(max_restarts):
        with span("ke.segment"):
            V, T, B_q = _dispatch(segment, V, T, jnp.asarray(j0))
        n_matvec += m - j0 * p
        with span("ke.restart"):
            theta, S, resid, V_restart, T_new, all_conv, healthy = _dispatch(
                _restart_math, V, T, B_q, jnp.asarray(tol_eff, dtype),
                s=s, keep=keep, m=m, p=p, which=which,
                resid_floor_rel=resid_floor_rel)
        if callback is not None:
            with span("ke.checkpoint"):
                callback(k_restart, V, T, m)
        # one fetch for both fused verdicts (same dispatch budget as the
        # single-scalar convergence test this replaces)
        with span("ke.sync", restart=k_restart):
            conv_ok, health_ok = map(bool, fetch((all_conv, healthy)))
        # a poisoned restart state stops the loop too: no burning restarts
        # on NaNs (a NaN residual never compares <= thresh)
        if conv_ok or not health_ok:
            n_restart, converged = k_restart + 1, conv_ok and health_ok
            break
        # thick restart
        V, T = V_restart, T_new
        j0 = keep // p

    with span("ke.ritz"):
        evecs = V[:, :m] @ S[:, :s]
        if health_ok:
            evecs = orthonormalize(evecs)
    return LanczosResult(theta[:s], evecs, n_matvec, n_restart, converged,
                         resid[:s], healthy=health_ok)


# ---------------------------------------------------------------------------
# fully jitted driver (fixed trip counts) for the batched/dry-run path
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("s", "m", "which", "max_restarts",
                                   "use_kernel", "p", "filter_degree",
                                   "compute_dtype"))
def lanczos_solve_jit(op: Operator, v0: jax.Array, s: int, m: int,
                      which: str = "SA", max_restarts: int = 50,
                      use_kernel: bool = False, p: int = 1,
                      filter_degree: int = 0,
                      compute_dtype: str | None = None):
    """lax.while_loop thick-restart block Lanczos; ONE XLA program.

    ``v0`` is (n,) for p == 1 or an (n, p) starting block. Returns
    (evals (s,), evecs (n, s), n_restarts_used, converged, healthy) —
    ``healthy`` is the fused finite-sentinel verdict, and an unhealthy
    state also terminates the while loop (a NaN residual never passes
    the convergence compare, so without it the loop would spin to
    max_restarts on a poisoned basis). Shares the
    block segment/restart core with ``lanczos_solve`` — the two drivers
    cannot drift. ``compute_dtype`` (a dtype NAME, static) demotes the
    operator application only, exactly as in ``lanczos_solve``.
    """
    n = v0.shape[0]
    dtype = v0.dtype
    eps = jnp.finfo(dtype).eps
    assert m % p == 0, (m, p)
    keep, _ = restart_schedule(s, m, p)
    resid_floor_rel = 0.0
    if compute_dtype is not None and jnp.dtype(compute_dtype) != dtype:
        cdtype = jnp.dtype(compute_dtype)
        op_c = jax.tree_util.tree_map(lambda a: a.astype(cdtype), op)
        matvec = lambda X: apply_op(  # noqa: E731
            op_c, X.astype(cdtype), use_kernel=use_kernel).astype(dtype)
        resid_floor_rel = 8.0 * float(jnp.finfo(cdtype).eps)
    else:
        matvec = lambda X: apply_op(op, X, use_kernel=use_kernel)  # noqa: E731

    X0 = v0[:, None] if v0.ndim == 1 else v0
    assert X0.shape == (n, p), (X0.shape, p)
    if filter_degree > 0:
        from .filtering import (chebyshev_filter, estimate_bounds,
                                filter_interval, probe_steps)
        kb = probe_steps(s, n)
        theta_p, beta_k = estimate_bounds(matvec, X0[:, 0], kb)
        a, b, a0 = filter_interval(theta_p, beta_k, s, which)
        X0 = chebyshev_filter(matvec, X0, filter_degree, a, b, a0)
    Q0, _ = qr_posdiag(X0)
    V0 = jnp.zeros((n, m + p), dtype).at[:, :p].set(Q0)
    T0 = jnp.zeros((m + p, m + p), dtype)

    def cond(state):
        k, _, _, _, converged, healthy, _, _ = state
        return (k < max_restarts) & jnp.logical_not(converged) & healthy

    def body(state):
        k, V, T, j0_val, _, _, _, _ = state
        V, T, B_q = _segment_impl(matvec, V, T, j0_val, p)
        theta, S, resid, V_restart, T_new, conv, healthy = _restart_math(
            V, T, B_q, eps, s, keep, m, p, which,
            resid_floor_rel=resid_floor_rel
        )
        evecs = V[:, :m] @ S[:, :s]
        return (k + 1, V_restart, T_new, jnp.asarray(keep // p), conv,
                healthy, theta[:s], evecs)

    state0 = (jnp.asarray(0), V0, T0, jnp.asarray(0), jnp.asarray(False),
              jnp.asarray(True), jnp.zeros((s,), dtype),
              jnp.zeros((n, s), dtype))
    k, V, T, j0_val, converged, healthy, evals, evecs = jax.lax.while_loop(
        cond, body, state0
    )
    q = orthonormalize(evecs)
    return evals, q, k, converged, healthy
