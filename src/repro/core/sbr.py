"""TT1/TT2 — two-stage tridiagonalization (SBR toolbox analogue).

Stage 1 (``reduce_to_band``, DSYRDB): dense -> band of width w via panel QR +
compact-WY two-sided updates, compiled as ONE program: the panel
factorization is a single fused launch (``kernels/house_panel`` — Pallas on
TPU, the identical jnp expression elsewhere), the trailing update runs in
SYR2K form (one rank-2w update per panel, ``kernels/syr2k`` on TPU), and
the sweep over panels is a ``lax.fori_loop`` over a small static
shrinking-window ladder — so a full reduction costs O(1) host dispatches
instead of the O(n/w) round trips of the per-panel host loop (kept as
``reduce_to_band_stepwise``, the baseline of ``benchmarks/bench_sbr.py``;
``dispatch_count()`` exposes the difference to the regression tests).
All flops are GEMMs (the BLAS-3 / MXU-friendly profile that motivates
variant TT in the paper) and Q1 is accumulated *explicitly* by GEMMs, as
the paper describes (two matrix products per panel). Stage 1 is NOT cheap:
once the bulge chase went wavefront (PR 4) it is the dominant stage of a
TT solve, which is why the sweep structure above matters. The window
ladder is auto-sized by :func:`default_n_chunks` — at small n the ladder's
extra windows cost more than the ~1/3 flop saving buys (BENCH_sbr measured
speedup_tt1 = 0.52 at n=128/w=8), so small problems run ``n_chunks=1``.

Stage 2 (``band_to_tridiag``, DSBRDT): band -> tridiagonal via Givens bulge
chasing over COMPACT band storage (see ``core.band_storage``), scheduled in
Schwarz/Kaufman wavefront sweeps: per time step, every in-flight column
sweep advances one chase step, and all of those rotations — provably
disjoint by the stagger of the schedule — are applied as ONE fused batched
update (``kernels/rot_apply``: a Pallas kernel on TPU, the identical
vectorized XLA expression elsewhere). The chase only touches the O(n w)
band; the (c, s) stream is RECORDED per pass and replayed by the same
blocked kernel afterwards — onto Q1^T in sweep-major batches for the
explicit-Q API (:func:`band_to_tridiag`), or onto the thin (n, s)
eigenvector slab (:func:`apply_q2`, the production path: O(n^2 s log w)
instead of O(n^3 log w) when s << n).

The dense-storage one-rotation-per-dispatch reference implementation is
kept as ``band_to_tridiag_dense`` (the parity oracle and the baseline in
``benchmarks/bench_sbr.py``; it is the code the old O(10 s @ n=256) TT2
measurements came from).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.house_panel.ops import house_panel
from repro.kernels.rot_apply.ops import rot_apply

from .band_storage import clean_band, pack_band, unpack_band
from .instrument import DispatchCounter
from .linalg_utils import (
    apply_wy_two_sided_syr2k,
    extract_tridiag,
    givens,
    rotate_cols,
    rotate_rows,
    symmetrize,
    wy_syr2k_panel,
)
from .precision import matmul_acc


class BandResult(NamedTuple):
    Wb: jax.Array  # (w+1, n) packed band (see core.band_storage), W = Q1^T C Q1
    Q1: jax.Array  # (n, n) explicit orthogonal factor

    def dense(self) -> jax.Array:
        """The banded matrix expanded to dense (n, n) — tests/benchmarks."""
        return unpack_band(self.Wb)


# dispatch accounting (observability + the regression tests' hook): the
# counter makes "fused sweep = O(1), stepwise loop = O(n/w)" testable
_dispatch = DispatchCounter()

#: host->device dispatches issued by ``reduce_to_band`` /
#: ``reduce_to_band_stepwise`` since the last ``reset_dispatch_count()``
dispatch_count = _dispatch.count
reset_dispatch_count = _dispatch.reset


def _chunk_bounds(n_panels: int, n_chunks: int):
    """Static panel ranges for the shrinking-window ladder."""
    n_chunks = max(1, min(n_chunks, n_panels))
    bounds = [round(c * n_panels / n_chunks) for c in range(n_chunks + 1)]
    return [(bounds[c], bounds[c + 1]) for c in range(n_chunks)
            if bounds[c + 1] > bounds[c]]


def _n_panels(n: int, w: int) -> int:
    return len(range(0, max(n - w - 1, 0), w))


#: the window ladder is a measured pessimization when the problem is small
#: or the windows are panel-starved (the extra window programs cost more
#: than the ~1/3 flop saving buys): BENCH_sbr measured speedup_tt1 = 0.52
#: at n=128/w=8 and 0.66 at n=256/w=32 (6 panels over 4 windows), vs 3.4x
#: at n=256/w=8 (30 panels) and 1.8-2.5x everywhere at n=512
_WINDOW_MIN_N = 256        # below: never ladder
_WINDOW_AUTO_N = 512       # at/above: always ladder
_WINDOW_MIN_PANELS = 16    # in between: need enough panels to amortize


def default_n_chunks(n: int, w: int) -> int:
    """Auto-sized shrinking-window ladder: up to 4 trailing windows once
    the problem is big enough (``n >= 512``, or ``n >= 256`` with at least
    16 panels); 1 (full-matrix updates) otherwise."""
    n_panels = _n_panels(n, w)
    if n_panels == 0:
        return 1
    if n >= _WINDOW_AUTO_N or (n >= _WINDOW_MIN_N
                               and n_panels >= _WINDOW_MIN_PANELS):
        return min(4, n_panels)
    return 1


def _wy_rank2_update(Mt: jax.Array, V: jax.Array, T: jax.Array) -> jax.Array:
    """SYR2K-form two-sided update; the rank-2w product goes through the
    fused ``kernels/syr2k`` Pallas kernel where ``kernels.dispatch`` picks
    it (a TPU, f32/bf16 operands: one HBM round trip per C tile) and the
    identical jnp expression elsewhere."""
    if dispatch.use_pallas(Mt.dtype):
        from repro.kernels.syr2k.ops import syr2k
        Z = wy_syr2k_panel(Mt, V, T)
        return symmetrize(syr2k(Mt, V, Z, alpha=-1.0))
    return apply_wy_two_sided_syr2k(Mt, V, T)


@partial(jax.jit, static_argnames=("w", "n_chunks"))
def _reduce_to_band_program(C: jax.Array, w: int, n_chunks: int) -> BandResult:
    """The whole stage-1 sweep as ONE compiled program (see reduce_to_band)."""
    n = C.shape[0]
    Q1_0 = jnp.eye(n, dtype=C.dtype)
    n_panels = _n_panels(n, w)
    if n_panels == 0:
        return BandResult(Wb=pack_band(C, w, symmetrize=True), Q1=Q1_0)

    M, Q1 = C, Q1_0
    for p0, p1 in _chunk_bounds(n_panels, n_chunks):
        o = p0 * w           # window origin (static)
        S = n - o            # window size (static)

        def body(p, carry, o=o, S=S):
            Mt, Q1t = carry
            c0 = p * w - o                       # panel start inside window
            E = jax.lax.dynamic_slice(Mt, (0, c0), (S, w))
            V, T = house_panel(E, c0 + w)        # one fused panel launch
            Mt = _wy_rank2_update(Mt, V, T)
            # explicit Q1 accumulation (two GEMMs per panel, paper Sec. 2.2)
            Q1t = Q1t - matmul_acc(matmul_acc(matmul_acc(Q1t, V), T), V.T)
            return Mt, Q1t

        Mt = jax.lax.slice(M, (o, o), (n, n))
        Q1t = jax.lax.slice(Q1, (0, o), (n, n))
        Mt, Q1t = jax.lax.fori_loop(p0, p1, body, (Mt, Q1t))
        M = jax.lax.dynamic_update_slice(M, Mt, (o, o))
        Q1 = jax.lax.dynamic_update_slice(Q1, Q1t, (0, o))
    return BandResult(Wb=pack_band(M, w, symmetrize=True), Q1=Q1)


def reduce_to_band(C: jax.Array, w: int = 32,
                   n_chunks: int | None = None) -> BandResult:
    """Stage 1: Q1^T C Q1 = W with bandwidth w. Panel QR + WY updates.

    The ENTIRE sweep — panel factorization (``kernels/house_panel``),
    T-build, SYR2K-form trailing update, Q1 accumulation — is one jitted
    program: panels are grouped into a small static ladder of trailing
    windows (the reflectors of panel k are masked below row ``(k+1) w``,
    so the two-sided update acts as identity before the window and the
    (S, S) trailing slice is the only data it can change), and within one
    window the panel loop is a ``fori_loop`` with FIXED-shape bodies (one
    compile per window size, ``n_chunks`` sizes total). ``n_chunks=None``
    auto-sizes the ladder via :func:`default_n_chunks`; ``n_chunks=1``
    is the full-(n, n) masked behavior (and the right choice at small n).

    Returns the band in packed (w+1, n) storage (``BandResult.Wb``) plus the
    explicit Q1. Costs O(1) host dispatches per sweep (``dispatch_count()``;
    the per-panel host loop survives as :func:`reduce_to_band_stepwise`).
    """
    if n_chunks is None:
        n_chunks = default_n_chunks(C.shape[0], w)
    return _dispatch(_reduce_to_band_program, C, w=w, n_chunks=n_chunks)


# per-panel jitted pieces of the stepwise baseline (compile once each)
_jit_slice_cols = jax.jit(
    lambda M, c0, w: jax.lax.dynamic_slice(M, (0, c0), (M.shape[0], w)),
    static_argnames=("w",))
_jit_house_panel = jax.jit(house_panel)
_jit_wy_update = jax.jit(apply_wy_two_sided_syr2k)
_jit_wy_right = jax.jit(
    lambda Q, V, T: Q - matmul_acc(matmul_acc(matmul_acc(Q, V), T), V.T))
_jit_pack = jax.jit(lambda M, w: pack_band(M, w, symmetrize=True),
                    static_argnames=("w",))


def reduce_to_band_stepwise(C: jax.Array, w: int = 32) -> BandResult:
    """The old per-panel HOST loop: one panel slice + QR + trailing update +
    Q1 accumulation dispatched per panel (O(n/w) host round trips).

    Numerically the same sweep as :func:`reduce_to_band` with
    ``n_chunks=1``; kept as the dispatch-overhead baseline for
    ``benchmarks/bench_sbr.py --quick`` and the dispatch-count regression
    tests — do not use it in production paths.
    """
    n = C.shape[0]
    M, Q1 = C, jnp.eye(n, dtype=C.dtype)
    for k in range(_n_panels(n, w)):
        c0 = k * w
        E = _dispatch(_jit_slice_cols, M, jnp.asarray(c0), w)
        V, T = _dispatch(_jit_house_panel, E, jnp.asarray(c0 + w))
        M = _dispatch(_jit_wy_update, M, V, T)
        Q1 = _dispatch(_jit_wy_right, Q1, V, T)
    return BandResult(Wb=_dispatch(_jit_pack, M, w), Q1=Q1)


class TridiagFromBandResult(NamedTuple):
    d: jax.Array   # (n,)
    e: jax.Array   # (n-1,)
    Q: jax.Array   # (n, n) accumulated Q1*Q2


class BandChaseResult(NamedTuple):
    """Chase output with the rotation stream kept implicit.

    ``cs[i]`` is the (J+1, K0+1, 2) (c, s) table of the i-th executed pass
    (bandwidths ``_executed_passes(n, w)``, i.e. b = w..2 skipping the
    degenerate ones); slot (j, k) is chase step k of column j's sweep,
    unused slots hold the identity rotation. Feed to :func:`apply_q2` /
    :func:`accumulate_q2` — O(n w + n^2 log w) storage instead of an
    (n, n) explicit Q2.
    """
    d: jax.Array
    e: jax.Array
    cs: Tuple[jax.Array, ...]


# ---------------------------------------------------------------------------
# TT2: wavefront bulge chasing over packed band storage
# ---------------------------------------------------------------------------
#
# Schwarz bandwidth-decrement sweeps b = w..2. In the b-pass, column j's
# sweep annihilates W[j+b, j] and chases the resulting bulge down in steps
# of b: chase step k rotates the plane (r-1, r) with r = j + (k+1) b. A
# rotation at center r touches only matrix indices [r-b-2, r+b+1], so two
# in-flight sweeps whose centers stay >= 2b+4 apart commute EXACTLY (they
# update disjoint entries — the wavefront reordering agrees with the
# sequential order to rounding noise). Starting column j at time step g*j
# with stagger g = 2 + ceil(5/b) makes consecutive active centers differ by
# g*b - 1 >= 2b + 4, so at every time step ALL in-flight rotations form one
# disjoint wavefront -> one fused rot_apply per side of the band windows.
#
# Q2 is NOT carried through the chase: the (c, s) stream is recorded and
# replayed sweep-major (all rotations of one sweep touch pairwise-disjoint
# row pairs — they are b >= 2 apart — so a whole sweep is again one fused
# rot_apply), in chase order onto Q1^T for the explicit Q, or in reverse
# order onto the (n, s) Ritz slab for the cheap production back-transform.

_P_LEFT = 2  # left column margin of the padded chase storage


def _executed_passes(n: int, w: int):
    return [b for b in range(w, 1, -1) if n - b > 0]


def _pass_schedule(n: int, b: int):
    """Static schedule of the bandwidth-b pass: (stagger, steps, lanes, J, K0)."""
    J = n - b                      # columns j = 0..J-1 annihilate W[j+b, j]
    g = 2 + -(-5 // b)             # smallest g with g*b - 1 >= 2b + 4
    K0 = (n - 1 - b) // b + 1      # chase steps of the longest (first) sweep
    T_pass = g * (J - 1) + 1       # last column starts at g(J-1), runs 1 step
    G = K0 // g + 1                # max simultaneously active sweeps
    return g, T_pass, G, J, K0


def _chase_pass(Wp: jax.Array, b: int, w: int, n: int):
    """One wavefront bandwidth-decrement pass (bandwidth b -> b-1).

    ``Wp`` is (w+2, n_pad) packed band storage (one spare diagonal for the
    bulge, zero padding on both column edges — corner windows read/write
    zeros there, which is self-preserving). Returns the updated band and
    the recorded (J+1, K0+1, 2) rotation table of the pass.
    """
    g, T_pass, G, J, K0 = _pass_schedule(n, b)
    L = 2 * b + 4                  # local window: columns [r-b-2, r+b+1]
    npad = Wp.shape[1]
    dump = npad - L                # all-zero dump window for inactive lanes

    # static gather/scatter index templates
    pgrid = jnp.arange(L)[:, None]
    qgrid = jnp.arange(L)[None, :]
    dd = jnp.abs(pgrid - qgrid)                     # (L, L) |row - col|
    mm = jnp.minimum(pgrid, qgrid)                  # (L, L) min(row, col)
    dvalid = dd <= w + 1
    dclip = jnp.clip(dd, 0, w + 1)
    drow = jnp.arange(w + 2)[:, None]               # (w+2, 1)
    qcol = jnp.arange(L)[None, :]                   # (1, L)
    in_win = (drow + qcol) < L                      # packed entry inside window
    rowsel = jnp.clip(drow + qcol, 0, L - 1)
    qcols = jnp.broadcast_to(qcol, (w + 2, L))
    larange = jnp.arange(L)

    # (c, s) table; unused slots stay at the identity rotation
    CS0 = jnp.zeros((J + 1, K0 + 1, 2), Wp.dtype).at[..., 0].set(1.0)

    def step(t, carry):
        Wp, CS = carry
        # wavefront lane decode: lane l rides column jtop - l
        jtop = jnp.minimum(t // g, J - 1)
        j = jtop - jnp.arange(G)
        k = t - g * j                                   # chase step of lane
        Kj = (n - 1 - j - b) // b + 1                   # sweep length of col j
        active = (j >= 0) & (k >= 0) & (k < Kj)
        r = j + (k + 1) * b                             # rotation plane (r-1, r)
        sk = (k > 0).astype(j.dtype)                    # bulge (1) vs first (0)
        i0 = jnp.where(active, r - b - 2 + _P_LEFT, dump)

        # gather each lane's local dense (L, L) window from packed storage
        colidx = i0[:, None, None] + mm                 # (G, L, L)
        local = jnp.where(dvalid, Wp[dclip, colidx], 0.0)

        # rotation params: annihilate local[b+2, 2-sk] against local[b+1, 2-sk]
        # (the in-band element for k=0, the chased bulge for k>0)
        tcol = (2 - sk)[:, None]
        a_piv = jnp.take_along_axis(local[:, b + 1, :], tcol, axis=1)[:, 0]
        a_ann = jnp.take_along_axis(local[:, b + 2, :], tcol, axis=1)[:, 0]
        cth, sth = givens(a_piv, a_ann)
        cs = jnp.stack([cth, sth], axis=1)              # (G, 2)
        CS = CS.at[jnp.where(active, j, J),
                   jnp.where(active, k, K0)].set(cs)

        # two-sided rotation of local rows/cols (b+1, b+2) — one wavefront,
        # one fused rot_apply per side
        rows = rot_apply(local[:, b + 1: b + 3, :], cs)
        local = local.at[:, b + 1: b + 3, :].set(rows)
        cols = rot_apply(jnp.swapaxes(local[:, :, b + 1: b + 3], 1, 2), cs)
        local = local.at[:, :, b + 1: b + 3].set(jnp.swapaxes(cols, 1, 2))

        # scatter the packed windows back (lane windows are disjoint)
        wcols = i0[:, None] + larange[None, :]          # (G, L)
        old_win = jnp.moveaxis(Wp[:, wcols], 1, 0)      # (G, w+2, L)
        new_win = jnp.where(in_win, local[:, rowsel, qcols], old_win)
        Wp = Wp.at[:, wcols].set(jnp.moveaxis(new_win, 0, 1))
        return Wp, CS

    Wp, CS = jax.lax.fori_loop(0, T_pass, step, (Wp, CS0))
    # annihilated diagonals carry O(eps) residue; zero them so the next pass
    # sees an exact bandwidth-(b-1) matrix
    Wp = Wp.at[b:, :].set(0.0)
    return Wp, CS


def _band_chase_core(Wb: jax.Array, w: int):
    """Run all bandwidth passes; returns (d, e, per-pass rotation tables)."""
    wp1, n = Wb.shape
    assert wp1 == w + 1, (Wb.shape, w)
    # padded chase storage: one bulge diagonal, zero margins on both column
    # edges (left: windows of the first sweeps start at r-b-2 = -2; right:
    # corner windows overhang by up to b+1, plus a dump window for masked
    # wavefront lanes)
    npad = _P_LEFT + n + 3 * w + 8
    Wp = jnp.zeros((w + 2, npad), Wb.dtype)
    Wp = Wp.at[: w + 1, _P_LEFT: _P_LEFT + n].set(clean_band(Wb))
    cs_list = []
    for b in _executed_passes(n, w):
        Wp, CS = _chase_pass(Wp, b, w, n)
        cs_list.append(CS)
    d = Wp[0, _P_LEFT: _P_LEFT + n]
    e = Wp[1, _P_LEFT: _P_LEFT + n - 1]
    return d, e, tuple(cs_list)


def _replay_pass(Xp: jax.Array, CS: jax.Array, b: int, n: int,
                 reverse: bool):
    """Apply one pass's recorded rotations to padded row storage ``Xp``.

    Sweep-major: all K0 rotations of one column sweep touch pairwise
    disjoint row pairs (planes are b >= 2 apart), so a sweep is ONE fused
    rot_apply over (K0, 2, cols) gathers; sweeps run forward (chase order,
    for accumulating Q2 onto Q^T) or backward (for Q2 @ Z, where the last
    recorded rotation acts first and each (c, s) flips to (c, -s)).
    """
    J, K0 = CS.shape[0] - 1, CS.shape[1] - 1
    nr = Xp.shape[0] - 2
    ks = jnp.arange(K0)

    def body(i, Xp):
        j = (J - 1 - i) if reverse else i
        r = j + (ks + 1) * b
        valid = r < n
        rows = jnp.where(valid[:, None],
                         jnp.stack([r - 1, r], axis=1),
                         nr + jnp.array([0, 1]))
        cs = CS[j, :K0]
        if reverse:
            cs = cs * jnp.array([1.0, -1.0], cs.dtype)
        Xp = Xp.at[rows].set(rot_apply(Xp[rows], cs))
        return Xp

    return jax.lax.fori_loop(0, J, body, Xp)


def _pad_rows(X: jax.Array):
    return jnp.zeros((X.shape[0] + 2, X.shape[1]), X.dtype).at[:-2].set(X)


@partial(jax.jit, static_argnames=("w",))
def band_chase(Wb: jax.Array, w: int) -> BandChaseResult:
    """TT2 without explicit Q: chase the band, keep the rotation stream.

    The production form of stage 2: the chase itself costs O(n^2 w) on
    O(n w) storage, and the recorded stream back-transforms an (n, s) slab
    via :func:`apply_q2` for O(n^2 s log w) — no (n, n) Q2 is ever formed.
    """
    if w <= 1 or Wb.shape[1] <= 2:
        n = Wb.shape[1]
        e = Wb[1, : n - 1] if w >= 1 else jnp.zeros((n - 1,), Wb.dtype)
        return BandChaseResult(d=Wb[0, :], e=e, cs=())
    d, e, cs = _band_chase_core(Wb, w)
    return BandChaseResult(d=d, e=e, cs=cs)


@partial(jax.jit, static_argnames=("w",))
def apply_q2(chase: BandChaseResult, Z: jax.Array, w: int) -> jax.Array:
    """Compute Q2 @ Z from the recorded rotation stream (Z is (n, s)).

    Rotations recorded as Q <- Q G must hit Z as G_N ... G_1 applied
    left-to-right from the LAST one, i.e. passes in reverse (b = 2..w),
    sweeps within a pass in reverse, with each (c, s) transposed.
    """
    n = Z.shape[0]
    passes = _executed_passes(n, w)
    assert len(passes) == len(chase.cs), (len(passes), len(chase.cs))
    Zp = _pad_rows(Z)
    for b, CS in zip(reversed(passes), reversed(chase.cs)):
        Zp = _replay_pass(Zp, CS, b, n, reverse=True)
    return Zp[:-2]


@partial(jax.jit, static_argnames=("w",))
def accumulate_q2(chase: BandChaseResult, Q1: jax.Array,
                  w: int) -> jax.Array:
    """Explicit Q1 @ Q2 by replaying the stream onto Q1^T in chase order."""
    n = Q1.shape[1]
    passes = _executed_passes(n, w)
    assert len(passes) == len(chase.cs), (len(passes), len(chase.cs))
    Qtp = _pad_rows(Q1.T)
    for b, CS in zip(passes, chase.cs):
        Qtp = _replay_pass(Qtp, CS, b, n, reverse=False)
    return Qtp[:-2].T


@partial(jax.jit, static_argnames=("w",))
def band_to_tridiag(Wb: jax.Array, Q1: jax.Array,
                    w: int) -> TridiagFromBandResult:
    """Stage 2 with explicit Q: wavefront chase + blocked Q2 accumulation.

    ``Wb`` is the symmetric band in ``core.band_storage`` packed layout
    (``Wb[d, i] = W[i+d, i]``); ``Q1`` is the (n, n) factor the chase
    rotations are accumulated into from the right (pass ``jnp.eye(n)`` to
    get Q2 alone). Numerically this is the same rotation sequence as
    :func:`band_to_tridiag_dense` — the wavefront schedule only reorders
    provably-disjoint rotations — but it runs on O(n w) storage with fused
    batched updates instead of one masked (n, n) row/column update per
    rotation. When only s << n back-transformed vectors are needed, use
    :func:`band_chase` + :func:`apply_q2` and skip the O(n^3) explicit
    accumulation entirely.
    """
    chase = band_chase(Wb, w)
    if not chase.cs:
        return TridiagFromBandResult(d=chase.d, e=chase.e, Q=Q1)
    Q = accumulate_q2(chase, Q1, w)
    return TridiagFromBandResult(d=chase.d, e=chase.e, Q=Q)


@partial(jax.jit, static_argnames=("w",), donate_argnums=())
def band_to_tridiag_dense(W: jax.Array, Q1: jax.Array,
                          w: int) -> TridiagFromBandResult:
    """Dense-storage TT2 reference: one masked row/col rotation per step.

    The flop-shape-faithful but dispatch-bound original implementation
    (every rotation is an O(n) masked update of the full (n, n) matrix and
    of Q, serialized in a while_loop). Kept as the parity oracle for
    :func:`band_to_tridiag` and as the baseline of
    ``benchmarks/bench_sbr.py``; the packed wavefront version above is the
    production path.
    """
    n = W.shape[0]
    M = W
    Q = Q1
    dist = jnp.abs(jnp.arange(n)[:, None] - jnp.arange(n)[None, :])

    def chase_one(state):
        M, Q, r, c, b = state
        # annihilate M[r, c] with rows (r-1, r)
        a = M[r - 1, c]
        bb = M[r, c]
        cth, sth = givens(a, bb)
        M = rotate_rows(M, r - 1, r, cth, sth)
        M = rotate_cols(M, r - 1, r, cth, sth)
        # the (r-1, r)/(r, r-1) pair is the one entry the row-then-col
        # update rounds through two different expression orders; pin the
        # upper copy to the lower one so the matrix stays EXACTLY symmetric
        # (packed storage holds a single copy — without this the two
        # implementations diverge from an O(eps) asymmetry seed)
        M = M.at[r - 1, r].set(M[r, r - 1])
        Q = rotate_cols(Q, r - 1, r, cth, sth)
        # next bulge position
        c_new = r - 1
        r_new = r + b
        return M, Q, r_new, c_new, b

    def chase_cond(state):
        _, _, r, _, _ = state
        return r < n

    for b in range(w, 1, -1):
        def col_body(j, carry):
            M, Q = carry
            r0 = j + b
            state = (M, Q, r0, j, jnp.asarray(b))
            M, Q, _, _, _ = jax.lax.while_loop(chase_cond, chase_one, state)
            return M, Q

        if n - b > 0:
            M, Q = jax.lax.fori_loop(0, n - b, col_body, (M, Q))
            # the annihilated diagonals carry O(eps) residue; zero them so
            # the next sweep sees an exact bandwidth-(b-1) matrix (the same
            # invariant the packed wavefront chase maintains — this is what
            # keeps the two implementations in close agreement instead of
            # diverging through noise-conditioned rotations)
            M = jnp.where(dist >= b, 0.0, M)

    d, e = extract_tridiag(symmetrize(M))
    return TridiagFromBandResult(d=d, e=e, Q=Q)


def two_stage_tridiagonalize(C: jax.Array, w: int = 32):
    """TT1+TT2 composed: returns (d, e, Q) with Q^T C Q = T, Q explicit."""
    band = reduce_to_band(C, w=w)
    return band_to_tridiag(band.Wb, band.Q1, w)
