"""TD2/TT3 — symmetric tridiagonal eigensolver for s << n wanted pairs.

The paper uses MR^3 (DSTEMR); its defining property for the study is that the
tridiagonal stage costs O(ns) and is negligible. MR^3's recursive
representation tree is sequential and branch-divergent — a poor fit for
TPU/SIMD — so we realize the same O(ns) contract with the classic
embarrassingly-parallel pair (see DESIGN.md §3.3):

  * eigenvalues:  Sturm-count bisection, vectorized across all wanted indices
  * eigenvectors: shifted inverse iteration with pivoted tridiagonal LU
                  (DGTTRF-style), vmapped across eigenvalues, with
                  cluster-wise reorthogonalization (DSTEIN-style).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .linalg_utils import gershgorin_bounds


def _pivmin(d: jax.Array, e: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(d)), jnp.max(jnp.abs(e)) if e.size else 0.0)
    scale = jnp.maximum(scale, 1.0)
    return jnp.finfo(d.dtype).tiny / jnp.finfo(d.dtype).eps * scale


def sturm_count(d: jax.Array, e: jax.Array, x: jax.Array,
                unroll: int = 1) -> jax.Array:
    """Number of eigenvalues of tridiag(d, e) strictly below x (scalar x).

    ``unroll`` unrolls the sequential Sturm recurrence ``unroll`` rows per
    scan step — pure loop unrolling, so the result is bitwise identical for
    every value; ``kernels/tridiag_eig`` uses it to amortize the per-step
    loop overhead that dominates this stage off-TPU.
    """
    pivmin = _pivmin(d, e)
    e2 = jnp.concatenate([jnp.zeros((1,), d.dtype), e * e])

    def body(carry, inp):
        q_prev, count = carry
        di, ei2 = inp
        q_safe = jnp.where(jnp.abs(q_prev) < pivmin,
                           jnp.where(q_prev < 0, -pivmin, pivmin), q_prev)
        q = (di - x) - ei2 / q_safe
        count = count + (q < 0).astype(jnp.int32)
        return (q, count), None

    init = (jnp.ones((), d.dtype), jnp.zeros((), jnp.int32))
    (q, count), _ = jax.lax.scan(body, init, (d, e2), unroll=unroll)
    # first step used q_prev=1 with e2=0 so it's exact
    return count


def sturm_counts(d: jax.Array, e: jax.Array, xs: jax.Array,
                 unroll: int = 1) -> jax.Array:
    """``sturm_count`` vectorized over a batch of shift points."""
    return jax.vmap(lambda x: sturm_count(d, e, x, unroll=unroll))(xs)


@partial(jax.jit, static_argnames=("max_iters", "unroll"))
def bisect_eigenvalues(d: jax.Array, e: jax.Array, ks: jax.Array,
                       max_iters: int = 80, unroll: int = 1) -> jax.Array:
    """k-th smallest eigenvalues, 0-indexed by the int array ``ks``.

    ``ks`` may be in any order — each lane bisects its own index
    independently and ``lam[i]`` answers ``ks[i]`` as given. (Downstream
    ``inverse_iteration`` is NOT order-agnostic: its gap-based clustering
    needs sorted shifts, which is why ``eigh_tridiag_selected``
    sorts-and-restores.) ``unroll`` is bitwise-neutral loop unrolling of
    the Sturm scans (see ``sturm_count``).
    """
    lo0, hi0 = gershgorin_bounds(d, e)
    lo = jnp.full(ks.shape, lo0, d.dtype)
    hi = jnp.full(ks.shape, hi0, d.dtype)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = sturm_counts(d, e, mid, unroll=unroll)
        go_right = cnt <= ks  # lambda_k >= mid
        lo = jnp.where(go_right, mid, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, max_iters, body, (lo, hi))
    return 0.5 * (lo + hi)


def _gttrf_gtts2(d: jax.Array, e: jax.Array, lam: jax.Array, b: jax.Array):
    """Solve (T - lam I) x = b with partial pivoting (DGTTRF + DGTTS2).

    Sequential lax.scan factorization; pivots clamped away from zero so that
    inverse iteration at a converged eigenvalue stays finite (DSTEIN-style).
    """
    n = d.shape[0]
    dtype = d.dtype
    pivmin = _pivmin(d, e)
    diag = d - lam
    if n == 1:
        dsafe = jnp.where(jnp.abs(diag[0]) < pivmin, pivmin, diag[0])
        return b / dsafe

    sub = e            # (n-1,) subdiagonal entries (row i+1, col i)
    sup = e            # (n-1,) superdiagonal
    sup_next = jnp.concatenate([sup[1:], jnp.zeros((1,), dtype)])  # du(i+1), 0 last

    def fact_body(carry, inp):
        dcur, ducur = carry
        dl_i, dnext, dunext = inp
        no_swap = jnp.abs(dcur) >= jnp.abs(dl_i)
        # --- no-swap branch
        dsafe = jnp.where(jnp.abs(dcur) < pivmin,
                          jnp.where(dcur < 0, -pivmin, pivmin), dcur)
        fact_ns = dl_i / dsafe
        # --- swap branch
        dlsafe = jnp.where(jnp.abs(dl_i) < pivmin,
                           jnp.where(dl_i < 0, -pivmin, pivmin), dl_i)
        fact_sw = dcur / dlsafe

        D_i = jnp.where(no_swap, dcur, dl_i)
        DU_i = jnp.where(no_swap, ducur, dnext)
        DU2_i = jnp.where(no_swap, 0.0, dunext)
        L_i = jnp.where(no_swap, fact_ns, fact_sw)
        dcur_new = jnp.where(no_swap, dnext - fact_ns * ducur,
                             ducur - fact_sw * dnext)
        ducur_new = jnp.where(no_swap, dunext, -fact_sw * dunext)
        return (dcur_new, ducur_new), (D_i, DU_i, DU2_i, L_i, no_swap)

    (d_last, _), (D, DU, DU2, L, no_swap) = jax.lax.scan(
        fact_body, (diag[0], sup[0]), (sub, diag[1:], sup_next)
    )
    D = jnp.concatenate([D, d_last[None]])  # (n,)

    # forward substitution with the recorded pivoting pattern
    def fwd_body(bcur, inp):
        b_next, L_i, ns = inp
        b_i = jnp.where(ns, bcur, b_next)
        bcur_new = jnp.where(ns, b_next - L_i * bcur, bcur - L_i * b_next)
        return bcur_new, b_i

    b_last, b_out = jax.lax.scan(fwd_body, b[0], (b[1:], L, no_swap))
    y = jnp.concatenate([b_out, b_last[None]])  # (n,)

    # back substitution: x_i = (y_i - DU_i x_{i+1} - DU2_i x_{i+2}) / D_i
    Dsafe = jnp.where(jnp.abs(D) < pivmin,
                      jnp.where(D < 0, -pivmin, pivmin), D)
    DUp = jnp.concatenate([DU, jnp.zeros((1,), dtype)])
    DU2p = jnp.concatenate([DU2, jnp.zeros((1,), dtype)])

    def back_body(carry, inp):
        x1, x2 = carry  # x_{i+1}, x_{i+2}
        y_i, du_i, du2_i, ds_i = inp
        x_i = (y_i - du_i * x1 - du2_i * x2) / ds_i
        return (x_i, x1), x_i

    inps = (y[::-1], DUp[::-1], DU2p[::-1], Dsafe[::-1])
    _, xs = jax.lax.scan(back_body, (jnp.zeros((), dtype), jnp.zeros((), dtype)), inps)
    return xs[::-1]


def _cluster_ids(lam: jax.Array, scale: jax.Array) -> jax.Array:
    """DSTEIN-style clustering: eigenvalues closer than 1e-3*scale share a group."""
    gaps = jnp.diff(lam)
    new_cluster = (gaps > 1e-3 * scale).astype(jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(new_cluster)])


def _mgs_clustered(X: jax.Array, cid: jax.Array) -> jax.Array:
    """Orthogonalize columns of X within clusters (masked MGS), renormalize."""
    s = X.shape[1]

    def body(i, X):
        xi = X[:, i]
        mask = (jnp.arange(s) < i) & (cid == cid[i])
        coeff = (X.T @ xi) * mask  # (s,)
        xi = xi - X @ coeff
        xi = xi / jnp.maximum(jnp.linalg.norm(xi), jnp.finfo(X.dtype).tiny)
        return X.at[:, i].set(xi)

    return jax.lax.fori_loop(1, s, body, X)


@partial(jax.jit, static_argnames=("iters",))
def inverse_iteration(d: jax.Array, e: jax.Array, lam: jax.Array,
                      key: jax.Array, iters: int = 3) -> jax.Array:
    """Eigenvectors for the (sorted) eigenvalues `lam`; returns Z (n, s)."""
    n = d.shape[0]
    s = lam.shape[0]
    scale = jnp.maximum(jnp.max(jnp.abs(d)), jnp.max(jnp.abs(e)) if e.size else 0.0)
    cid = _cluster_ids(lam, scale)
    X = jax.random.normal(key, (n, s), d.dtype)
    X = X / jnp.linalg.norm(X, axis=0, keepdims=True)

    solve_batch = jax.vmap(_gttrf_gtts2, in_axes=(None, None, 0, 1), out_axes=1)

    def one_round(_, X):
        X = solve_batch(d, e, lam, X)
        X = X / jnp.maximum(jnp.linalg.norm(X, axis=0, keepdims=True),
                            jnp.finfo(d.dtype).tiny)
        X = _mgs_clustered(X, cid)
        return X

    X = jax.lax.fori_loop(0, iters, one_round, X)
    return X


class TridiagEigResult(NamedTuple):
    lam: jax.Array  # (s,) eigenvalues, ascending within selection
    Z: jax.Array    # (n, s) eigenvectors of T


def default_tridiag_method(dtype=jnp.float32, n: int = 0,
                           s: int = 0) -> str:
    """Per-call default for ``eigh_tridiag_selected``: the Pallas kernels
    where ``kernels.dispatch`` compiles them (a TPU, an f32 tridiagonal
    whose resident columns fit VMEM), the fused-XLA batched program (which
    beats interpret-mode Pallas by orders of magnitude) everywhere else —
    including every f64 TT3/TD2 on a TPU."""
    from repro.kernels import dispatch
    from repro.kernels.tridiag_eig.ops import (bisect_vmem_bytes,
                                               invit_vmem_bytes)
    vmem = max(bisect_vmem_bytes(n), invit_vmem_bytes(n, s))
    return "kernel" if dispatch.use_pallas(dtype, vmem) else "batched"


def eigh_tridiag_selected(d: jax.Array, e: jax.Array, ks: jax.Array,
                          key: jax.Array | None = None,
                          method: str | None = None) -> TridiagEigResult:
    """Selected eigenpairs of tridiag(d, e) at indices ``ks`` (any order).

    ``ks`` is sorted internally and the result unpermuted, so
    ``lam[i], Z[:, i]`` answer ``ks[i]`` as given — ``inverse_iteration``'s
    gap-based clustering and masked MGS assume ascending shifts, and
    feeding them unsorted eigenvalues silently mis-clusters and skips
    reorthogonalization (the shuffled-``ks`` regression in
    tests/test_tridiag_eig.py).

    method:
      None      — per-call choice (:func:`default_tridiag_method`):
                  'kernel' where the Pallas kernels compile for the
                  platform, dtype and size, 'batched' elsewhere.
      'scan'    — the legacy two-program baseline (bisection jit + inverse
                  iteration jit, unroll=1 Sturm scans).
      'batched' — ONE fused program from ``kernels.tridiag_eig.ops`` with
                  unrolled Sturm scans; bitwise-identical values,
                  measurably faster (the BENCH_tridiag gate), and the
                  path ``core.batched`` vmaps.
      'kernel'  — the Pallas kernels (interpret mode off-TPU), for parity
                  tests and TPU execution.
    """
    if method is None:
        method = default_tridiag_method(d.dtype, d.shape[0], jnp.shape(ks)[0])
    if key is None:
        key = jax.random.PRNGKey(12021)
    ks = jnp.asarray(ks)
    order = jnp.argsort(ks)
    inv = jnp.argsort(order)
    ks_sorted = ks[order]
    if method == "scan":
        lam = bisect_eigenvalues(d, e, ks_sorted)
        Z = inverse_iteration(d, e, lam, key)
    elif method == "batched":
        from repro.kernels.tridiag_eig.ops import tridiag_eig_batched
        lam, Z = tridiag_eig_batched(d, e, ks_sorted, key)
    elif method == "kernel":
        from repro.kernels.tridiag_eig.ops import tridiag_eig_kernel
        lam, Z = tridiag_eig_kernel(d, e, ks_sorted, key)
    else:
        raise ValueError(f"unknown tridiag-eig method: {method!r}")
    return TridiagEigResult(lam=lam[inv], Z=Z[:, inv])
