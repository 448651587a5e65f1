"""Dispatch wrappers for the batched tridiagonal eigensolver (TT3/TD2).

Two execution paths behind one contract:

``tridiag_eig_batched`` — the XLA path every backend gets: bisection and
inverse iteration fused into ONE jitted program, with the Sturm scans
unrolled ``unroll`` rows per step. Unrolling is bitwise-neutral (plain
loop unrolling), so this path returns exactly the values of the legacy
two-program baseline while cutting the scan's per-step loop overhead —
the margin the ``BENCH_tridiag.json --quick`` gate pins at n=2048, s=64.
It is plain traceable jnp, so ``core.batched`` vmaps it into bucket
pipelines and ``dist.eigensolver`` calls it inside ``shard_map``.

``tridiag_eig_kernel`` — the Pallas path: one ``bisect_sturm_pallas``
launch for all indices' intervals and one ``invit_pallas`` launch for all
shifted solves + cluster MGS (interpret mode off-TPU). The ops wrappers
own the padding contract: rows to the sublane multiple (8) with
decoupling pads (Sturm pads sit above the spectrum; solve pads carry
``e = 0`` seams and zero start rows), lanes to 128 with out-of-band
cluster ids and zero start columns.

Like ``kernels/house_panel``: ``force_kernel=True`` exercises the Pallas
path off-TPU (interpret mode unless ``force_interpret=False``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.linalg_utils import gershgorin_bounds
from repro.core.tridiag_eig import (_cluster_ids, _pivmin, bisect_eigenvalues,
                                    inverse_iteration)
from repro.kernels import dispatch

from .kernel import bisect_sturm_pallas, invit_pallas

#: Sturm-scan unroll of the fused XLA path — measured sweet spot on host
#: backends (per-step loop overhead amortized over 16 rows; larger factors
#: start losing to instruction-cache pressure).
SCAN_UNROLL = 16


def _pad_up(k: int, mult: int) -> int:
    return k + (-k) % mult


def bisect_vmem_bytes(n: int) -> int:
    """The Sturm kernel keeps d and e^2 resident as (N, 1) f32 columns,
    each padded to the 128-lane tile."""
    return 2 * _pad_up(n, 8) * 128 * 4


def invit_vmem_bytes(n: int, s: int) -> int:
    """The inverse-iteration kernel keeps the (N, S) start block, its
    result and four (N, S) scratch factors resident, plus d and e."""
    N, S = _pad_up(n, 8), _pad_up(s, 128)
    return (6 * N * S + 2 * N * 128) * 4


# ------------------------------------------------------------ fused XLA --

@functools.partial(jax.jit, static_argnames=("max_iters", "iters", "unroll"))
def tridiag_eig_batched(d: jax.Array, e: jax.Array, ks: jax.Array,
                        key: jax.Array, max_iters: int = 80, iters: int = 3,
                        unroll: int = SCAN_UNROLL):
    """ONE fused program: unrolled Sturm bisection + inverse iteration.

    ``ks`` must be sorted ascending (``eigh_tridiag_selected`` owns the
    sort-and-restore). Bitwise-equal to the 'scan' baseline — ``unroll``
    only changes how many recurrence rows share a loop iteration.
    """
    lam = bisect_eigenvalues(d, e, ks, max_iters=max_iters, unroll=unroll)
    Z = inverse_iteration(d, e, lam, key, iters=iters)
    return lam, Z


# --------------------------------------------------------- Pallas launch --

def bisect_sturm(d: jax.Array, e: jax.Array, ks: jax.Array,
                 max_iters: int = 80, force_kernel: bool = False,
                 force_interpret: bool | None = None) -> jax.Array:
    """Eigenvalues at indices ``ks`` — Pallas kernel on TPU, unrolled XLA
    scan elsewhere. Both agree bitwise with ``bisect_sturm_ref``."""
    n, s = d.shape[0], ks.shape[0]
    if not dispatch.use_pallas(d.dtype, bisect_vmem_bytes(n),
                               force=force_kernel):
        return bisect_eigenvalues(d, e, ks, max_iters=max_iters,
                                  unroll=SCAN_UNROLL)
    interpret = dispatch.interpret(force_interpret)
    N, S = _pad_up(n, 8), _pad_up(s, 128)
    lo0, hi0 = gershgorin_bounds(d, e)
    piv = _pivmin(d, e)
    e2 = jnp.concatenate([jnp.zeros((1,), d.dtype), e * e])
    # pad rows sit strictly above every probed shift (x <= hi0), with a
    # zero e2 seam: their Sturm terms stay positive and count nothing
    d_pad = jnp.concatenate([d, jnp.full((N - n,), hi0 + 1.0, d.dtype)])
    e2_pad = jnp.concatenate([e2, jnp.zeros((N - n,), d.dtype)])
    ks_pad = jnp.concatenate([ks.astype(jnp.int32),
                              jnp.zeros((S - s,), jnp.int32)])
    lam = bisect_sturm_pallas(
        d_pad[:, None], e2_pad[:, None], ks_pad[None, :],
        jnp.full((1, S), lo0, d.dtype), jnp.full((1, S), hi0, d.dtype),
        jnp.full((1, S), piv, d.dtype), max_iters=max_iters,
        interpret=interpret)
    return lam[0, :s]


def invit_batched(d: jax.Array, e: jax.Array, lam: jax.Array,
                  key: jax.Array, iters: int = 3,
                  force_kernel: bool = False,
                  force_interpret: bool | None = None) -> jax.Array:
    """Eigenvectors for SORTED shifts ``lam`` — Pallas kernel on TPU,
    the vmapped-scan LU elsewhere."""
    n, s = d.shape[0], lam.shape[0]
    if not dispatch.use_pallas(d.dtype, invit_vmem_bytes(n, s),
                               force=force_kernel):
        return inverse_iteration(d, e, lam, key, iters=iters)
    interpret = dispatch.interpret(force_interpret)
    N, S = _pad_up(n, 8), _pad_up(s, 128)
    scale = jnp.maximum(jnp.max(jnp.abs(d)),
                        jnp.max(jnp.abs(e)) if e.size else 0.0)
    cid = _cluster_ids(lam, scale)
    piv = _pivmin(d, e)
    X0 = jax.random.normal(key, (n, s), d.dtype)
    X0 = X0 / jnp.linalg.norm(X0, axis=0, keepdims=True)
    d_pad = jnp.concatenate([d, jnp.ones((N - n,), d.dtype)])
    # e_pad[i] couples rows i and i+1; zeros from row n-1 on decouple the
    # padding block entirely (its solve rows start and stay zero)
    e_pad = jnp.zeros((N,), d.dtype).at[:n - 1].set(e) if n > 1 \
        else jnp.zeros((N,), d.dtype)
    lam_pad = jnp.concatenate([lam, jnp.full((S - s,), lam[-1], d.dtype)])
    cid_pad = jnp.concatenate([cid, s + jnp.arange(S - s, dtype=jnp.int32)])
    X0_pad = jnp.zeros((N, S), d.dtype).at[:n, :s].set(X0)
    Z = invit_pallas(d_pad[:, None], e_pad[:, None], lam_pad[None, :],
                     cid_pad[None, :], jnp.full((1, S), piv, d.dtype),
                     X0_pad, iters=iters, interpret=interpret)
    return Z[:n, :s]


def tridiag_eig_kernel(d: jax.Array, e: jax.Array, ks: jax.Array,
                       key: jax.Array, max_iters: int = 80, iters: int = 3,
                       force_interpret: bool | None = None):
    """Full TT3 through the two Pallas launches (interpret off-TPU)."""
    lam = bisect_sturm(d, e, ks, max_iters=max_iters, force_kernel=True,
                       force_interpret=force_interpret)
    Z = invit_batched(d, e, lam, key, iters=iters, force_kernel=True,
                      force_interpret=force_interpret)
    return lam, Z


__all__ = ["tridiag_eig_batched", "tridiag_eig_kernel", "bisect_sturm",
           "invit_batched", "bisect_vmem_bytes", "invit_vmem_bytes",
           "SCAN_UNROLL"]
