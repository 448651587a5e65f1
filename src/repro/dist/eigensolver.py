"""Distributed KE and TT pipelines over a 2-D device mesh.

Stage-for-stage the paper's variants, with each dense stage routed
through ``sharded_la``:

KE (``solve_ke_distributed``):
  GS1  U = dist_cholesky(B)                  (row-block panels)
  GS2  C = U^{-T} A U^{-1}                   (two dist_trsm_left_t solves)
  KE1  communication-avoiding block Lanczos  (ONE shard_map-ped jitted
       program per thick restart — the whole s-step segment loop plus the
       restart math — with TWO collectives per (n, p) block step: the
       matvec psum over 'model' and the row all_gather that doubles as
       the broadcast; see ``ke_restart_program``. An optional Chebyshev
       prep program filters the starting block so clustered spectra
       converge inside the restart budget.)
  BT1  X = U^{-1} Y                          (dist_trsm_left)

TT (``solve_tt_distributed``, the ELPA2-style two-stage path):
  GS1/GS2 as above, then
  TT1  dense -> band of width w              (ONE shard_map-ped program
       for the whole sweep: all_gather'd panel -> fused compact-WY QR ->
       sharded SYR2K trailing update + Q1 accumulation, all BLAS-3 and
       O(1) host dispatches — see ``dist_reduce_to_band``)
  TT2  band -> tridiagonal                   (replicated wavefront bulge
       chase on packed O(n w) band storage; the rotation stream is
       recorded, not accumulated — Q1 never leaves the mesh and no
       (n, n) Q2 is formed)
  TT3  bisection + inverse iteration         (spectrum-partitioned: each
       device owns a contiguous slice of the wanted indices — EleMRRR-
       style — bisects and inverse-iterates it locally, and two kinds of
       all_gather reassemble lam and Z; see ``dist_tridiag_eig``)
  TT4  Y = Q1 (Q2 Z)                         (rotation replay on the thin
       slab + collective-free panel matmul against the mesh-resident Q1)
  BT1  X = U^{-1} Y                          (dist_trsm_left)

The Krylov stage shares ``core.lanczos``'s block segment and restart math
(``_segment_impl`` / ``_restart_math``) — the distributed path supplies a
matvec closure instead of duplicating the restart logic.
``core.gsyeig.solve(..., mesh=...)`` dispatches here.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.filtering import (chebyshev_filter, estimate_bounds,
                                  filter_interval, probe_steps)
from repro.core.instrument import (DispatchCounter, fetch, span, stage,
                                   timed, wait)
from repro.core.lanczos import (_restart_math, _segment_impl,
                                default_subspace, restart_schedule)
from repro.core.linalg_utils import symmetrize
from repro.core.looped import orthonormalize, pad_identity, qr_posdiag
from repro.core.precision import (compute_dtype, exact_matmuls,
                                  validate_precision)
from repro.core.sbr import (_jit_house_panel, _jit_pack, _jit_slice_cols,
                            _n_panels, apply_q2, band_chase)
from repro.core.tridiag_eig import (TridiagEigResult, _cluster_ids,
                                    _gttrf_gtts2, _mgs_clustered,
                                    bisect_eigenvalues,
                                    eigh_tridiag_selected)
from repro.kernels.tridiag_eig.ops import SCAN_UNROLL
from .partitioning import auto_axes
from .sharded_la import (_n_row_shards, _row_axes, _row_spec, _row_sharded,
                         band_sweep_program, dist_apply_wy_right,
                         dist_apply_wy_two_sided, dist_cholesky,
                         dist_panel_matmul, dist_trsm_left,
                         dist_trsm_left_t)


def _standard_form(mesh, A, B, times):
    """GS1 + GS2 (shared by KE and TT): B = U^T U, C = U^{-T} A U^{-1}
    via two transposed panel solves, resymmetrized."""
    U = timed(times, "GS1", dist_cholesky, mesh, B)
    T1 = timed(times, "GS2", dist_trsm_left_t, mesh, U, A)
    C = timed(times, "GS2", lambda t: dist_trsm_left_t(mesh, U, t.T).T, T1)
    return U, 0.5 * (C + C.T)


def _mesh_tiling(mesh, n: int):
    """(row_spec, gather_axes, n_row_shards, model_size) plus whether n
    tiles evenly over both mesh dimensions (the fused programs' layout)."""
    rs = _row_spec(mesh)
    row_axes = _row_axes(mesh)
    ax = row_axes if len(row_axes) > 1 else (row_axes[0] if row_axes else None)
    R = max(_n_row_shards(mesh), 1)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    cm = sizes.get("model", 1)
    return rs, ax, R, cm, (n % R == 0 and n % cm == 0)


def _fused_block_matvec(c_blk, ncm: int, ax):
    """The communication-avoiding W = C X on an (n, p) replicated block,
    from inside a shard_map region with C 2-D-sharded (rows x 'model').

    Exactly TWO collectives: each device multiplies its (nloc, ncm) tile
    against its 'model' slice of X, ONE psum over 'model' completes the
    row block, and ONE all_gather over the row axes rebuilds the
    replicated (n, p) result — which doubles as the broadcast for the
    redundantly-computed orthogonalization/restart math (the
    ``band_sweep_program`` trick), so the O(n m p) small-matrix work costs
    zero extra collectives. Compare one psum per matvec (2 p collectives
    per block step) in the old per-``dist_symv`` path.
    """
    def matvec(X):
        mi = jax.lax.axis_index("model")
        Xs = jax.lax.dynamic_slice_in_dim(X, mi * ncm, ncm, axis=0)
        Wp = jax.lax.psum(c_blk @ Xs, "model")
        if ax is not None:
            Wp = jax.lax.all_gather(Wp, ax, axis=0, tiled=True)
        return Wp
    return matvec


@functools.lru_cache(maxsize=None)
def ke_restart_program(mesh, n: int, p: int, m: int, s: int, keep: int,
                       which: str, dtype_name: str):
    """ONE ``shard_map``-ped jitted program per thick restart (KE1).

    The whole block-Lanczos segment — every (n, p) block step with its
    two-collective fused matvec, the two-pass re-orthogonalization, and
    the residual-block QR — runs as a ``lax.fori_loop`` inside a single
    shard_map region, followed by the replicated restart math (eigh of
    T_m, Ritz residual bounds, thick-restart state) and the Ritz-vector
    assembly. The host issues one dispatch per restart and fetches a
    single convergence scalar: the same dispatch discipline
    ``band_sweep_program`` gives TT1, applied to the Krylov side.

    Returns a jitted ``(C, V, T, j0, tol_eff) ->
    (theta (s,), resid (s,), V', T', converged, healthy, evecs (n, s))``
    callable; V/T are donated. Requires n divisible by both mesh tilings
    (``solve_ke_distributed`` zero-pads the operator to such an n).
    """
    rs, ax, R, cm, ok = _mesh_tiling(mesh, n)
    assert ok, (n, R, cm)
    ncm = n // cm

    def ke_restart(c_blk, V, T, j0, tol_eff):
        matvec = _fused_block_matvec(c_blk, ncm, ax)
        V, T, B_q = _segment_impl(matvec, V, T, j0, p)
        # the restart math carries the fused health sentinel — the
        # finite-state verdict rides out of the SAME program as the
        # convergence scalar, zero extra dispatches
        theta, S, resid, V_r, T_new, conv, healthy = _restart_math(
            V, T, B_q, tol_eff, s=s, keep=keep, m=m, p=p, which=which)
        evecs = orthonormalize(V[:, :m] @ S[:, :s])
        return theta[:s], resid[:s], V_r, T_new, conv, healthy, evecs

    prog = shard_map(ke_restart, mesh=mesh,
                     in_specs=(P(rs, "model"), P(None, None), P(None, None),
                               P(), P()),
                     out_specs=(P(None), P(None), P(None, None),
                                P(None, None), P(), P(), P(None, None)),
                     check_vma=False)
    return jax.jit(prog, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def ke_prep_program(mesh, n: int, p: int, kb: int, degree: int, s: int,
                    which: str, dtype_name: str):
    """ONE fused program for the Chebyshev prep: the kb-step bound probe,
    the interval selection, the degree-d filter recurrence on the (n, p)
    starting block, and its orthonormalization — every matvec the fused
    two-collective kind, every small step replicated. One host dispatch
    total, so filtering never reintroduces a per-matvec round trip."""
    rs, ax, R, cm, ok = _mesh_tiling(mesh, n)
    assert ok, (n, R, cm)
    ncm = n // cm

    def ke_prep(c_blk, X0):
        matvec = _fused_block_matvec(c_blk, ncm, ax)
        theta, beta_k = estimate_bounds(matvec, X0[:, 0], kb)
        a, b, a0 = filter_interval(theta, beta_k, s, which)
        Xf = chebyshev_filter(matvec, X0, degree, a, b, a0)
        Q0, _ = qr_posdiag(Xf)
        return Q0

    prog = shard_map(ke_prep, mesh=mesh,
                     in_specs=(P(rs, "model"), P(None, None)),
                     out_specs=P(None, None),
                     check_vma=False)
    return jax.jit(prog)


@exact_matmuls
def solve_ke_distributed(
    mesh,
    A: jax.Array,
    B: jax.Array,
    s: int,
    m: Optional[int] = None,
    which: str = "smallest",
    tol: float = 0.0,
    max_restarts: int = 500,
    key: Optional[jax.Array] = None,
    return_info: bool = False,
    p: int = 4,
    filter_degree: int = 0,
    invert: bool = False,
    precision: str = "fp64",
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 2,
    resume: bool = False,
    preempt_after: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """s extremal eigenpairs of A X = B X Lambda on a 2-D device mesh.

    The Krylov stage is the communication-avoiding block Lanczos: one
    fused ``shard_map`` program per thick restart (``ke_restart_program``)
    with two collectives per (n, ``p``) block step. ``filter_degree > 0``
    Chebyshev-filters the starting block (one extra fused program);
    ``invert=True`` applies the paper's MD trick in-place — solve the
    inverse pair (B, A) for its LARGEST eigenpairs and map back — which is
    what makes the log-spaced MD spectrum converge fast at its tiny end.

    ``precision`` demotes the Krylov stage only (GS1/GS2/BT1 stay fp64):
    ``mixed`` runs the whole fused restart program — operand, basis and
    restart math — in fp32; ``fast`` keeps the basis fp32 but ships the
    sharded operand in bf16 (the matvec accumulates in fp32 via dtype
    promotion). The convergence test is floored at the demoted operand's
    attainable residual; callers recover fp64 accuracy by refinement
    (``core.refinement`` via ``gsyeig.solve(..., precision=...)``).

    Failure containment: ``checkpoint_dir`` persists the thick-restart
    state (V, T) through ``dist/checkpoint`` every ``checkpoint_every``
    restarts (atomic, ``checkpoint_keep`` newest retained);
    ``resume=True`` warm-starts from the newest committed checkpoint —
    the restart boundary is a pure function of (V, T), so a resumed
    solve on a DIFFERENT mesh (e.g. an ``elastic.plan_remesh``-shrunken
    one after host loss) reproduces the uninterrupted eigenvalues to
    collective-roundoff (the preemption-drill parity test pins 1e-12).
    ``preempt_after=k`` is the drill hook: raise
    ``resilience.faults.SimulatedPreemption`` after the k-th restart's
    checkpoint lands. ``info['healthy']`` carries the fused finite-state
    sentinel of the restart program.

    Returns ``(evals (s,) ascending, X (n, s) B-orthonormal)``; with
    ``return_info=True`` a third dict carries per-stage wall-clock times
    and Lanczos counters (n_matvec, n_restart, converged, healthy).
    """
    validate_precision(precision)
    mesh = auto_axes(mesh)
    demoted = precision != "fp64"
    cdtype = compute_dtype(precision)
    B_orig = B
    if invert:
        A, B = B, A
        which = "largest" if which == "smallest" else "smallest"
    n = A.shape[0]
    if m is None:
        m = default_subspace(s, n, p)
    assert m % p == 0, (m, p)
    if key is None:
        key = jax.random.PRNGKey(20120520)
    times = {}

    # an n that does not tile the mesh is padded to one that does: A with
    # zeros, B with an identity block. Then U and C are block-diagonal, C
    # with an exactly zero block, and the starting block is zero on the
    # padded rows — so the Krylov space never leaves the first n rows
    # (every matvec, re-orthogonalization and QR keeps them exactly zero)
    # and the padding changes no eigenpair of the solve
    rs, ax, R, cm, _ = _mesh_tiling(mesh, n)
    n_k = -(-n // math.lcm(R, cm)) * math.lcm(R, cm)
    if n_k != n:
        A = jnp.pad(A, ((0, n_k - n), (0, n_k - n)))
        B = pad_identity(B, n_k)
    U, C = _standard_form(mesh, A, B, times)
    arp_which = "SA" if which == "smallest" else "LA"
    # work dtype of the basis/restart math; the operand may sit lower
    wdtype = jnp.float32 if demoted else C.dtype
    keep, _ = restart_schedule(s, m, p)

    healthy = True
    resumed_from = None
    rep = NamedSharding(mesh, P(None, None))

    def padded(M):                  # (n, k) -> (n_k, k), replicated
        return jax.device_put(jnp.pad(M, ((0, n_k - n), (0, 0))), rep)

    with stage(times, "KE_iter"):
        # the Krylov operand lives 2-D-sharded: rows over data axes, cols
        # over 'model' — the layout the fused block matvec consumes
        with span("ke.place"):
            if demoted:
                C = C.astype(cdtype)
            dtype = C.dtype
            C = jax.device_put(C, NamedSharding(mesh, P(rs, "model")))
        dname = jnp.dtype(dtype).name
        n_matvec = 0
        with span("ke.prep"):
            X0 = padded(jax.random.normal(key, (n, p), wdtype))
            if filter_degree > 0:
                kb = probe_steps(s, n)
                prep = ke_prep_program(mesh, n_k, p, kb, filter_degree, s,
                                       arp_which, dname)
                Q0 = _dispatch(prep, C, X0)
                n_matvec += kb + filter_degree * p
            else:
                Q0, _ = qr_posdiag(X0)
        with span("ke.place"):
            V = jax.device_put(
                jnp.zeros((n_k, m + p), wdtype).at[:, :p].set(Q0), rep)
            T = jax.device_put(jnp.zeros((m + p, m + p), wdtype), rep)
        # the demoted operand floors the attainable residual at
        # ~eps(cdtype) * ||C||; ask for no more (core.lanczos uses the
        # same 8x floor on its local demoted path)
        eps = float(jnp.finfo(dtype).eps)
        eps_eff = 8.0 * eps if demoted else eps
        tol_eff = jnp.asarray(tol if tol > 0.0 else eps_eff, wdtype)
        prog = ke_restart_program(mesh, n_k, p, m, s, keep, arp_which, dname)
        j0 = 0
        k0 = 0
        converged = False
        if checkpoint_dir is not None and resume:
            from . import checkpoint as _ckpt
            # dict keys flatten sorted, so the template's {T, V} order
            # matches what save() wrote; V is stored unpadded, so a resume
            # may land on a mesh with another padding
            with span("ke.checkpoint"):
                got = _ckpt.load_latest(
                    checkpoint_dir,
                    {"T": jnp.zeros((m + p, m + p), wdtype),
                     "V": jnp.zeros((n, m + p), wdtype)})
            if got is not None:
                step, tree, extra = got
                with span("ke.place"):
                    V = padded(tree["V"])
                    T = jax.device_put(tree["T"], rep)
                j0 = int(extra.get("j", keep // p))
                k0 = int(step) + 1
                n_matvec = int(extra.get("n_matvec", n_matvec))
                resumed_from = int(step)
        n_restart = max_restarts
        for k_restart in range(k0, max_restarts):
            with span("ke.restart"):
                lam, resid, V, T, conv, healthy_dev, Y = _dispatch(
                    prog, C, V, T, jnp.asarray(j0), tol_eff)
            n_matvec += m - j0 * p
            j0 = keep // p
            # one fetch for both fused verdicts
            with span("ke.sync", restart=k_restart):
                conv_ok, health_ok = map(bool, fetch((conv, healthy_dev)))
            if (checkpoint_dir is not None
                    and k_restart % checkpoint_every == 0):
                # the POST-restart (V, T) — the state the next segment
                # consumes — so a resumed solve replays the identical
                # restart arithmetic
                from . import checkpoint as _ckpt
                with span("ke.checkpoint"):
                    _ckpt.save(checkpoint_dir, k_restart,
                               fetch({"V": V[:n], "T": T}),
                               extra={"kind": "ke_dist", "j": int(j0),
                                      "n_matvec": int(n_matvec)},
                               keep=checkpoint_keep)
            if (preempt_after is not None
                    and k_restart - k0 + 1 >= preempt_after):
                from repro.resilience.faults import SimulatedPreemption
                raise SimulatedPreemption(k_restart)
            if not health_ok:
                healthy = False
                n_restart = k_restart + 1
                break
            if conv_ok:
                converged = True
                n_restart = k_restart + 1
                break
        wait(Y)

    if demoted:
        lam, Y = lam.astype(A.dtype), Y.astype(A.dtype)
    order = jnp.argsort(lam)
    lam, Y = lam[order], Y[:, order]

    # BT1: X = U^{-1} Y
    X = timed(times, "BT1", dist_trsm_left, mesh, U, Y)[:n]

    if invert:
        with span("finalize"):
            lam = 1.0 / lam
            order = jnp.argsort(lam)
            lam, X = lam[order], X[:, order]
            from repro.core.residuals import b_normalize
            X = b_normalize(X, jax.device_put(
                B_orig, NamedSharding(mesh, P(None, None))))

    if return_info:
        info = {"stage_times": times, "n_matvec": int(n_matvec),
                "n_restart": int(n_restart),
                "converged": bool(converged), "healthy": bool(healthy),
                "p": int(p), "filter_degree": int(filter_degree),
                "precision": precision,
                "restart_program": {"n": int(n_k), "m": int(m),
                                    "keep": int(keep), "which": arp_which,
                                    "dtype": dname}}
        if resumed_from is not None:
            info["resumed_from"] = int(resumed_from)
        return lam, X, info
    return lam, X


# -------------------------------------------------------- TT pipeline -----

# the per-panel jitted pieces of the STEPWISE baseline (column slice, fused
# panel QR, band pack) come from core.sbr — one set of helpers serves both
# stepwise baselines. ``_jit_pack`` also packs the replicated band into
# compact (w+1, n) storage for the TT2 wavefront chase.
_jit_band_clean = jax.jit(
    lambda M, w: symmetrize(jnp.where(
        jnp.abs(jnp.arange(M.shape[0])[:, None]
                - jnp.arange(M.shape[0])[None, :]) <= w, M, 0.0)),
    static_argnames=("w",))


# dispatch accounting for the TT1 sweep, mirroring ``core.lanczos`` /
# ``core.sbr``: each jitted-program invocation counts 1, so the regression
# tests can pin "fused sweep = O(1), per-panel loop = O(n/w)"
_dispatch = DispatchCounter()

#: host->device dispatches issued by ``dist_reduce_to_band`` (and the
#: stepwise baseline) since the last ``reset_dispatch_count()``
dispatch_count = _dispatch.count
reset_dispatch_count = _dispatch.reset


def dist_reduce_to_band(mesh, C, w: int = 8):
    """TT1: distributed Q1^T C Q1 = W (bandwidth w) on row-sharded storage.

    The ENTIRE sweep is ONE ``shard_map``-ped jitted program
    (``sharded_la.band_sweep_program``): panel assembly by ``all_gather``,
    replicated compact-WY factorization (``kernels/house_panel``), the
    SYR2K-form sharded trailing update, and the in-place Q1 accumulation
    all run inside a single ``lax.fori_loop`` — O(1) host dispatches per
    reduction where the old per-panel host loop
    (:func:`dist_reduce_to_band_stepwise`) paid a Python round trip plus a
    fresh ``shard_map`` dispatch per panel, which ``BENCH_variant_race``
    measured as 13.4s of a 14.3s solve at n=128 on 8 host devices.

    Returns ``(W, Q1)`` both row-block-sharded on the mesh; W is
    band-masked (off-band entries exactly zero). Storage note: W stays in
    full dense (n, n) form while mesh-resident (row-block sharding needs
    the rectangular layout); ``solve_tt_distributed`` packs it into compact
    (w+1, n) band storage — averaging the triangles — right before the
    replicated TT2 wavefront chase (see ``core.band_storage``). When n is
    not divisible by the row-shard count R, C is embedded in a
    block-diagonal ``[[C, 0], [0, I]]`` of the next multiple of R — the
    padding rows carry identity reflectors (their panel tails are zero)
    and identity Q1/W blocks, so the sliced-back result is exactly the
    reduction of C and the sweep STAYS one fused program for every n
    (matching the 2-dispatch TT1 the cost model charges; ``shard_map``
    could not run a per-panel fallback on uneven shards anyway).
    """
    n = C.shape[0]
    R = max(_n_row_shards(mesh), 1)
    n_pad = -(-n // R) * R
    if n_pad != n:
        idx = jnp.arange(n, n_pad)
        C = jnp.zeros((n_pad, n_pad), C.dtype).at[:n, :n].set(C) \
            .at[idx, idx].set(1.0)
    row_sh = _row_sharded(mesh, C)
    M = jax.device_put(C, row_sh)
    Q1 = jax.device_put(jnp.eye(n_pad, dtype=C.dtype), row_sh)
    sweep = band_sweep_program(mesh, n_pad, w, jnp.dtype(C.dtype).name)
    W, Q1 = _dispatch(sweep, M, Q1)
    if n_pad != n:
        W, Q1 = W[:n, :n], Q1[:n, :n]
    return W, Q1


def dist_reduce_to_band_stepwise(mesh, C, w: int = 8):
    """The old per-panel HOST loop: gather panel -> replicated QR ->
    ``dist_apply_wy_two_sided`` / ``dist_apply_wy_right``, one fresh set of
    dispatches (and two host device_put round trips) per panel.

    Kept ONLY as the dispatch-overhead baseline for the regression tests —
    do not use it on the hot path (``dist_reduce_to_band`` handles every n,
    padding to the shard multiple when needed).
    """
    n = C.shape[0]
    row_sh = _row_sharded(mesh, C)
    rep = NamedSharding(mesh, P(None, None))
    M = jax.device_put(C, row_sh)
    Q1 = jax.device_put(jnp.eye(n, dtype=C.dtype), row_sh)
    for k in range(_n_panels(n, w)):
        c0 = k * w
        E = jax.device_put(_dispatch(_jit_slice_cols, M,
                             jnp.asarray(c0), w), rep)
        V, T = _dispatch(_jit_house_panel, E, jnp.asarray(c0 + w))
        V = jax.device_put(V, rep)
        M = _dispatch(dist_apply_wy_two_sided, mesh, M, V, T)
        Q1 = _dispatch(dist_apply_wy_right, mesh, Q1, V, T)
    W = jax.device_put(_dispatch(_jit_band_clean, M, w), row_sh)
    return W, Q1


@functools.lru_cache(maxsize=None)
def tt3_program(mesh, n: int, s_pad: int, max_iters: int, iters: int,
                unroll: int, dtype_name: str):
    """ONE ``shard_map``-ped jitted program for the spectrum-partitioned
    TT3 (EleMRRR-style, arXiv:1205.2107).

    The wanted-index axis is sharded over EVERY mesh axis: each device
    bisects its contiguous slice of ``ks`` with the unrolled Sturm scans
    (lanes are independent, so the partition is embarrassingly parallel),
    ONE all_gather reassembles the full sorted ``lam`` — which doubles as
    the broadcast for the replicated gap-based clustering (the
    ``band_sweep_program`` trick: redundant O(s) work, zero extra
    collectives) — and each inverse-iteration round factors/solves only
    the local shifted systems before an all_gather over the column axis
    rebuilds the block for the replicated cluster-wise MGS. That per-round
    gather is what keeps cross-shard clusters correct: a degenerate pair
    split across the slice boundary still reorthogonalizes every round,
    exactly like the replicated path — ``lam`` is BITWISE equal to
    ``eigh_tridiag_selected(..., method='batched')`` (each lane's Sturm
    arithmetic is independent of its neighbors), and ``Z`` agrees to the
    last bits: the only width-sensitive op is the column-norm reduction,
    whose vectorization may reassociate on narrow local slices (ulp-level,
    pinned <= 1e-12 by the parity tests and the bench gate).

    Collectives: 1 (lam) + ``iters`` (Z rounds). Requires ``s_pad``
    divisible by the device count (``dist_tridiag_eig`` owns the padding).

    Returns a jitted ``(d, e, ks_pad, X0) -> (lam (s_pad,), Z (n, s_pad))``
    callable; ``ks_pad`` sorted ascending, ``X0`` column-normalized with
    padding columns exactly zero (they solve to zero and drop out of every
    MGS sum, so real columns never see them).
    """
    axes = tuple(mesh.axis_names)
    part = axes if len(axes) > 1 else axes[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dev = 1
    for a in axes:
        n_dev *= sizes[a]
    assert s_pad % n_dev == 0, (s_pad, n_dev)
    s_loc = s_pad // n_dev

    def tt3_eig(d, e, ks_loc, X0):
        lam_loc = bisect_eigenvalues(d, e, ks_loc, max_iters=max_iters,
                                     unroll=unroll)
        lam = jax.lax.all_gather(lam_loc, part, axis=0, tiled=True)
        scale = jnp.maximum(jnp.max(jnp.abs(d)),
                            jnp.max(jnp.abs(e)) if e.size else 0.0)
        cid = _cluster_ids(lam, scale)
        # flat shard index in sharding order -> this device's column offset
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        col0 = idx * s_loc
        solve_batch = jax.vmap(_gttrf_gtts2, in_axes=(None, None, 0, 1),
                               out_axes=1)
        tiny = jnp.finfo(X0.dtype).tiny

        def one_round(_, X):
            X_loc = jax.lax.dynamic_slice_in_dim(X, col0, s_loc, axis=1)
            X_loc = solve_batch(d, e, lam_loc, X_loc)
            X_loc = X_loc / jnp.maximum(
                jnp.linalg.norm(X_loc, axis=0, keepdims=True), tiny)
            X = jax.lax.all_gather(X_loc, part, axis=1, tiled=True)
            return _mgs_clustered(X, cid)

        Z = jax.lax.fori_loop(0, iters, one_round, X0)
        return lam, Z

    prog = shard_map(tt3_eig, mesh=mesh,
                     in_specs=(P(None), P(None), P(part), P(None, None)),
                     out_specs=(P(None), P(None, None)),
                     check_vma=False)
    return jax.jit(prog)


def dist_tridiag_eig(mesh, d: jax.Array, e: jax.Array, ks: jax.Array,
                     key: Optional[jax.Array] = None, max_iters: int = 80,
                     iters: int = 3) -> TridiagEigResult:
    """Selected eigenpairs of tridiag(d, e) with the spectrum partitioned
    over the mesh (``tt3_program``); the distributed ``eigh_tridiag_selected``.

    Same contract: ``ks`` in any order, sorted internally and the result
    unpermuted. ``s`` is padded up to the device-count multiple with
    duplicates of the top index and zero start columns — both inert, both
    sliced off — so the index slices always tile the mesh. Eigenvalues
    are bitwise those of the replicated ``method='batched'`` path and
    eigenvectors match to the last bits (see ``tt3_program``).
    """
    if key is None:
        key = jax.random.PRNGKey(12021)
    d, e, ks = jnp.asarray(d), jnp.asarray(e), jnp.asarray(ks)
    n, s = d.shape[0], ks.shape[0]
    n_dev = int(mesh.devices.size)
    s_pad = -(-s // n_dev) * n_dev
    order = jnp.argsort(ks)
    inv = jnp.argsort(order)
    ks_sorted = ks[order]
    ks_pad = jnp.concatenate(
        [ks_sorted, jnp.full((s_pad - s,), ks_sorted[-1], ks_sorted.dtype)])
    X0 = jax.random.normal(key, (n, s), d.dtype)
    X0 = X0 / jnp.linalg.norm(X0, axis=0, keepdims=True)
    X0 = jnp.zeros((n, s_pad), d.dtype).at[:, :s].set(X0)
    prog = tt3_program(mesh, n, s_pad, max_iters, iters, SCAN_UNROLL,
                       jnp.dtype(d.dtype).name)
    lam, Z = _dispatch(prog, d, e, ks_pad, X0)
    return TridiagEigResult(lam=lam[:s][inv], Z=Z[:, :s][:, inv])


@exact_matmuls
def solve_tt_distributed(
    mesh,
    A: jax.Array,
    B: jax.Array,
    s: int,
    which: str = "smallest",
    band_width: int = 8,
    key: Optional[jax.Array] = None,
    return_info: bool = False,
    shard_tt3: bool = True,
    precision: str = "fp64",
) -> Tuple[jax.Array, jax.Array]:
    """s extremal eigenpairs of A X = B X Lambda via the distributed
    two-stage reduction (the paper's TT variant, ELPA2-style).

    The band reduction (TT1) and every O(n^3)/O(n^2 s) GEMM/TRSM stay on
    the mesh, and the tridiagonal eigensolver (TT3) is spectrum-partitioned
    over it (``dist_tridiag_eig``: per-device index slices, EleMRRR-style;
    ``shard_tt3=False`` falls back to the replicated fused path — same
    values bitwise). Only the bulge chase (TT2) runs replicated — the
    O(n^2 w) stage the paper measures as negligible.

    ``precision`` demotes the reduction stages (TT1/TT2/TT4) to the
    compute dtype of ``core.precision``; GS1/GS2, the tridiagonal
    eigensolve and BT1 stay fp64, and callers recover fp64 eigenpair
    accuracy via ``core.refinement`` (``gsyeig.solve(..., mesh=...,
    precision=...)`` does so automatically).

    Returns ``(evals (s,) ascending, X (n, s))``; with
    ``return_info=True`` a third dict carries per-stage wall-clock times.
    """
    validate_precision(precision)
    mesh = auto_axes(mesh)
    demoted = precision != "fp64"
    cdtype = compute_dtype(precision)
    n = A.shape[0]
    if key is None:
        key = jax.random.PRNGKey(20120520)
    times = {}

    U, C = _standard_form(mesh, A, B, times)
    if demoted:
        C = C.astype(cdtype)

    # TT1: dense -> band, Q1 stays mesh-resident
    W, Q1 = timed(times, "TT1", dist_reduce_to_band, mesh, C, band_width)

    # TT2: band -> tridiagonal, replicated (O(n^2 w) wavefront Givens work
    # over packed (w+1, n) band storage). No Q2 is materialized — the
    # rotation stream is recorded and replayed onto the thin Ritz slab in
    # TT4, so Q1 — the O(n^2) object — never gathers and Q2 never exists.
    rep = NamedSharding(mesh, P(None, None))
    W_rep = jax.device_put(W, rep)
    chase = timed(times, "TT2", lambda wr: band_chase(
        _jit_pack(wr, band_width), band_width), W_rep)

    # TT3: selected eigenpairs of the tridiagonal — each device bisects +
    # inverse-iterates its contiguous slice of the wanted indices (O(n s / P)
    # local work, 1 + iters collectives); replicated fallback is bitwise
    ks = jnp.arange(s) if which == "smallest" else jnp.arange(n - s, n)
    d64 = chase.d.astype(A.dtype)
    e64 = chase.e.astype(A.dtype)
    if shard_tt3:
        lam, Z = timed(times, "TT3", dist_tridiag_eig, mesh, d64, e64, ks,
                       key)
    else:
        lam, Z = timed(times, "TT3", eigh_tridiag_selected, d64, e64, ks,
                       key)

    # TT4: Y = Q1 (Q2 Z) — Q2 Z replays the recorded rotations over the
    # replicated (n, s) slab; the product against the row-sharded Q1 is a
    # collective-free panel matmul
    Zc = Z.astype(cdtype) if demoted else Z
    Y = timed(times, "TT4", lambda z: dist_panel_matmul(
        mesh, Q1, apply_q2(chase, z, band_width)), Zc)
    if demoted:
        Y = Y.astype(A.dtype)

    # BT1: X = U^{-1} Y
    X = timed(times, "BT1", dist_trsm_left, mesh, U, Y)

    if return_info:
        info = {"stage_times": times, "band_width": int(band_width),
                "precision": precision, "tt3_sharded": bool(shard_tt3)}
        return lam, X, info
    return lam, X
