"""Distributed BLAS-2/3 building blocks over a 2-D (data..., model) mesh.

The decomposition follows the multi-GPU ELPA2 / Solca-Schulthess playbook:

  * ``dist_symv`` / ``dist_gemm``  — explicit ``shard_map`` kernels: the
    operand matrix lives 2-D-sharded (row blocks over the data axes, column
    blocks over 'model'), each device multiplies its local tile, and one
    ``psum`` over 'model' finishes the row. ``*_rs`` variants replace the
    psum with ``psum_scatter`` so the output stays fully sharded (the
    collective is half the bytes — the right choice when the consumer is
    itself distributed).
  * ``dist_cholesky`` / ``dist_trsm_left_t`` — blocked panel algorithms
    (right-looking Cholesky, block forward/backward substitution: the
    one-``fori_loop`` programs of ``core.looped``, whose compile does not
    grow with n) on row-block-sharded operands; XLA's SPMD partitioner
    turns the panel broadcast into collectives per panel, matching the
    paper's "factor panel, broadcast, update trailing matrix" structure.

All entry points accept plain (even single-device) arrays and place them
onto the mesh themselves, so the same call sites work eagerly in tests and
traced inside jitted solvers.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cholesky import cholesky_blocked
from repro.core.looped import solve_upper_looped


def _row_spec(mesh):
    """The merged non-'model' axes: 'data', or ('pod', 'data') multi-pod."""
    rows = tuple(a for a in mesh.axis_names if a != "model")
    if not rows:
        return None
    return rows if len(rows) > 1 else rows[0]


def _row_model_spec(mesh):
    """Dim-0 spec splitting over every axis (rows then 'model')."""
    rows = tuple(a for a in mesh.axis_names if a != "model")
    axes = rows + (("model",) if "model" in mesh.axis_names else ())
    return axes if len(axes) > 1 else axes[0]


# ------------------------------------------------------------- matvec -----

def dist_symv(mesh, A, x):
    """y = A x with A 2-D-sharded (rows x 'model'), one psum per call.

    The KE1 hot loop: every Lanczos matvec in the distributed solver is
    exactly this kernel (2 n^2 flops spread over the whole mesh, n/R·n/C
    local tiles)."""
    rs = _row_spec(mesh)

    def local(a_blk, x_blk):
        return jax.lax.psum(a_blk @ x_blk, "model")

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, "model"), P("model")),
                     out_specs=P(rs))(A, x)


def dist_symv_rs(mesh, A, x):
    """Reduce-scatter symv: output stays sharded over (rows, 'model') —
    half the collective bytes of ``dist_symv`` when the consumer is itself
    a distributed kernel."""
    rs = _row_spec(mesh)

    def local(a_blk, x_blk):
        return jax.lax.psum_scatter(a_blk @ x_blk, "model", tiled=True)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, "model"), P("model")),
                     out_specs=P(_row_model_spec(mesh)))(A, x)


# --------------------------------------------------------------- gemm -----

def dist_gemm(mesh, A, B):
    """C = A B with A (rows x 'model')-sharded and B row-sharded over
    'model' (the contraction axis): local tile matmul + one psum."""
    rs = _row_spec(mesh)

    def local(a_blk, b_blk):
        return jax.lax.psum(a_blk @ b_blk, "model")

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, "model"), P("model", None)),
                     out_specs=P(rs, None))(A, B)


def dist_gemm_rs(mesh, A, B):
    """``dist_gemm`` with the psum replaced by a row-wise psum_scatter:
    the result stays fully sharded over (rows, 'model')."""
    rs = _row_spec(mesh)

    def local(a_blk, b_blk):
        return jax.lax.psum_scatter(a_blk @ b_blk, "model",
                                    scatter_dimension=0, tiled=True)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, "model"), P("model", None)),
                     out_specs=P(_row_model_spec(mesh), None))(A, B)


# -------------------------------------------------------------- syr2k -----

def dist_syr2k(mesh, C, V, W):
    """Rank-2w update C - V W^T - W V^T (DSYR2K, the band-reduction trailing
    update) with C row-block-sharded and V, W (n, w) panels.

    Each device updates its row block from its slice of V/W plus the full
    (replicated) panels — no collective at all: the panels are O(n w) and
    ride in replicated, so the O(n^2 w) flops are embarrassingly row-parallel.
    """
    rs = _row_spec(mesh)

    def local(c_blk, v_blk, w_blk, v_full, w_full):
        return c_blk - v_blk @ w_full.T - w_blk @ v_full.T

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, None), P(rs, None), P(rs, None),
                               P(None, None), P(None, None)),
                     out_specs=P(rs, None))(C, V, W, V, W)


def dist_panel_matmul(mesh, C, V):
    """X = C V with C row-block-sharded and V an (n, w) replicated panel:
    local tile matmul, output row-sharded, no collective."""
    rs = _row_spec(mesh)

    def local(c_blk, v_full):
        return c_blk @ v_full

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, None), P(None, None)),
                     out_specs=P(rs, None))(C, V)


def dist_apply_wy_two_sided(mesh, C, V, T):
    """Q^T C Q for symmetric row-sharded C, Q = I - V T V^T (compact WY).

    The two-sided update is refactored into SYR2K form (LAPACK DSYRDB):
    with X = C V and S = T^T (V^T X) T,

        Q^T C Q = C - Z V^T - V Z^T,   Z = X T - (1/2) V S,

    (S is symmetric because C is) so the distributed work is one
    panel matmul (X, row-parallel) plus one ``dist_syr2k``; the w x w
    couplings S, T stay replicated.
    """
    X = dist_panel_matmul(mesh, C, V)
    # panel couplings are O(n w) / O(w^2): compute replicated
    S = T.T @ (V.T @ X) @ T
    Z = X @ T - 0.5 * (V @ S)
    return dist_syr2k(mesh, C, V, Z)


def dist_apply_wy_right(mesh, M, V, T):
    """M Q = M - ((M V) T) V^T for row-sharded M — the explicit Q1
    accumulation of the band reduction (two GEMMs per panel, both local to
    each row block since V rides in replicated)."""
    rs = _row_spec(mesh)

    def local(m_blk, v_full, t):
        return m_blk - ((m_blk @ v_full) @ t) @ v_full.T

    return shard_map(local, mesh=mesh,
                     in_specs=(P(rs, None), P(None, None), P(None, None)),
                     out_specs=P(rs, None))(M, V, T)


# ------------------------------------------------- fused band-reduction ---

def _row_axes(mesh):
    return tuple(a for a in mesh.axis_names if a != "model")


@functools.lru_cache(maxsize=None)
def band_sweep_program(mesh, n: int, w: int, dtype_name: str):
    """ONE ``shard_map``-ped jitted program for the ENTIRE stage-1 sweep.

    The dispatch-light TT1: every panel iteration lives inside a
    ``lax.fori_loop`` in a single ``shard_map`` region, so a full reduction
    is one host dispatch instead of O(n/w) per-panel host round trips.
    Per panel, on each device's (n/R, n) row block:

      * the (n, w) panel columns are assembled by ONE ``all_gather`` over
        the row axes and factored to compact-WY (Y, T) via
        ``kernels/house_panel`` — replicated compute, O(n w^2), which makes
        the gather double as the panel broadcast (every shard ends up
        holding the same (Y, T) with zero extra collectives);
      * the trailing update runs in SYR2K form: X_blk = C_blk Y is local,
        the (w, w) coupling V^T X is one ``psum``, and the rank-2w update
        plus the explicit Q1 accumulation are local GEMMs (one more
        ``all_gather`` ships the O(n w) Z panel).

    Requires n divisible by the row-shard count (``dist_reduce_to_band``
    pads C to the shard multiple with an identity block otherwise, so the
    fused program serves every n). Returns a jitted
    ``(M, Q1) -> (W, Q1)`` callable on row-block-sharded storage; W comes
    back band-masked (|i-j| > w zeroed) but un-symmetrized — the packer
    averages the triangles when the band is replicated for TT2.
    """
    from repro.core.sbr import _n_panels
    from repro.kernels.house_panel.ops import house_panel

    rs = _row_spec(mesh)
    row_axes = _row_axes(mesh)
    ax = row_axes if len(row_axes) > 1 else row_axes[0]
    R = max(_n_row_shards(mesh), 1)
    assert n % R == 0, (n, R)
    nloc = n // R
    n_panels = _n_panels(n, w)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dtype = jnp.dtype(dtype_name)

    def tt1_sweep(m_blk, q_blk):
        # global row offset of this shard (row axes merge in mesh order)
        shard = jnp.zeros((), jnp.int32)
        for a in row_axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        r0 = shard * nloc

        def body(k, carry):
            m_blk, q_blk = carry
            c0 = k * w
            e_blk = jax.lax.dynamic_slice(m_blk, (0, c0), (nloc, w))
            E = jax.lax.all_gather(e_blk, ax, axis=0, tiled=True)
            V, T = house_panel(E, c0 + w)
            X_blk = m_blk @ V                                   # (nloc, w)
            V_blk = jax.lax.dynamic_slice(
                V, (r0, jnp.zeros((), r0.dtype)), (nloc, w))
            W_c = jax.lax.psum(V_blk.T @ X_blk, ax)             # (w, w)
            S = T.T @ W_c @ T
            Z_blk = X_blk @ T - 0.5 * (V_blk @ S)
            Z = jax.lax.all_gather(Z_blk, ax, axis=0, tiled=True)
            m_blk = m_blk - Z_blk @ V.T - V_blk @ Z.T
            # explicit Q1 accumulation (two local GEMMs per panel)
            q_blk = q_blk - ((q_blk @ V) @ T) @ V.T
            return m_blk, q_blk

        if n_panels:
            m_blk, q_blk = jax.lax.fori_loop(0, n_panels, body,
                                             (m_blk, q_blk))
        gi = r0 + jnp.arange(nloc, dtype=jnp.int32)[:, None]
        dist_band = jnp.abs(gi - jnp.arange(n, dtype=jnp.int32)[None, :])
        m_blk = jnp.where(dist_band <= w, m_blk, jnp.zeros((), dtype))
        return m_blk, q_blk

    sweep = shard_map(tt1_sweep, mesh=mesh,
                      in_specs=(P(rs, None), P(rs, None)),
                      out_specs=(P(rs, None), P(rs, None)),
                      check_vma=False)
    return jax.jit(sweep)


# ----------------------------------------------------- panel factorizations

def _n_row_shards(mesh) -> int:
    rows = tuple(a for a in mesh.axis_names if a != "model")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in rows:
        out *= sizes[a]
    return out


def _panel(mesh, n: int, block) -> int:
    if block is not None:
        return int(block)
    # one panel per row shard, clamped so tiny problems stay multi-panel
    # and huge dry-run problems don't unroll into enormous HLO
    return max(min(n // max(_n_row_shards(mesh), 1), 1024), 16)


def _trsm_lt_blocked(U, B, block: int):
    """Solve U^T W = B (U upper): block forward substitution."""
    return solve_upper_looped(U, B, trans=True, block=block)


def _trsm_l_blocked(U, B, block: int):
    """Solve U W = B (U upper): block backward substitution."""
    return solve_upper_looped(U, B, trans=False, block=block)


def _row_sharded(mesh, M):
    nd = getattr(M, "ndim", len(M.shape))
    spec = [None] * nd
    spec[0] = _row_spec(mesh)
    return NamedSharding(mesh, P(*spec))


@functools.lru_cache(maxsize=None)
def _jit_blocked(fn, block: int, out_sharding):
    """One jitted executable per (kernel, panel size, output layout):
    a fresh jax.jit per call would retrace/recompile every invocation.
    The program takes the kernel's name (a bare partial traces as
    ``jit(<unknown>)``)."""
    blocked = partial(fn, block=block)
    blocked.__name__ = blocked.__qualname__ = fn.__name__
    return jax.jit(blocked, out_shardings=out_sharding)


def dist_cholesky(mesh, B, block=None):
    """GS1: distributed B = U^T U on row-block-sharded storage.

    One panel per row shard by default; the SPMD partitioner lowers each
    ``U_k,: = U_kk^{-T} B_k,:`` panel solve into a broadcast of the
    factored diagonal block plus local trailing (SYRK) updates."""
    sh = _row_sharded(mesh, B)
    Bm = jax.device_put(B, sh)
    blk = _panel(mesh, B.shape[0], block)
    return _jit_blocked(cholesky_blocked, blk, sh)(Bm)


def dist_trsm_left_t(mesh, U, B, block=None):
    """GS2/BT: distributed solve of U^T W = B (U upper, left, transposed)."""
    sh = _row_sharded(mesh, B)
    Um = jax.device_put(U, _row_sharded(mesh, U))
    Bm = jax.device_put(B, sh)
    blk = _panel(mesh, U.shape[0], block)
    return _jit_blocked(_trsm_lt_blocked, blk, sh)(Um, Bm)


def dist_trsm_left(mesh, U, B, block=None):
    """BT1: distributed solve of U W = B (U upper, left) — the
    back-transform X = U^{-1} Y."""
    sh = _row_sharded(mesh, B)
    Um = jax.device_put(U, _row_sharded(mesh, U))
    Bm = jax.device_put(B, sh)
    blk = _panel(mesh, U.shape[0], block)
    return _jit_blocked(_trsm_l_blocked, blk, sh)(Um, Bm)
