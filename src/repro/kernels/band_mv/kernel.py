"""Pallas TPU kernel: symmetric BAND matrix-vector product in band storage.

This is the storage format variant TT's intermediate lives in (bandwidth w
after stage 1), and the building block for a TPU-native TT2: operating on
the (n, w+1) band instead of the (n, n) dense matrix cuts both HBM traffic
and the working set by n/w (= 500x at the paper's n=17k, w=32).

Layout: band[i, d] = A[i, i+d], d = 0..w (upper diagonals). For the matvec,
  y_i = sum_d band[i, d] x_{i+d} + sum_{d>=1} band[i-d, d] x_{i-d}.

Grid tiles rows (bm per step, w <= bm). The mirrored term needs a w-row
lookback; Pallas blocks cannot overlap, so the kernel receives the SAME band
array twice — the current tile and the previous tile (block index i-1,
clamped at 0; out-of-range rows are masked) — and gathers lookback rows from
their concatenation. x stays fully VMEM-resident (n <= ~1M f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import I0


def _band_mv_kernel(cur_ref, prev_ref, x_ref, o_ref, *, w: int, bm: int,
                    n: int):
    i = pl.program_id(0)
    row0 = i * bm
    cur = cur_ref[...]            # (bm, w+1) rows [row0, row0+bm)
    prev = prev_ref[...]          # (bm, w+1) rows [row0-bm, row0) (i>0)
    both = jnp.concatenate([prev, cur], axis=0)   # local row r -> r - row0 + bm
    x = x_ref[...]                # (n,)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm,), 0)

    acc = jnp.zeros((bm,), cur.dtype)
    for d in range(w + 1):
        # upper-diagonal term: band[i, d] * x[i+d]
        up_idx = jnp.clip(rows + d, 0, n - 1)
        up_ok = (rows + d) < n
        acc += jnp.where(up_ok, cur[:, d] * x[up_idx], 0.0)
        if d > 0:
            # mirrored term: band[i-d, d] * x[i-d]
            src = rows - d
            lo_ok = src >= 0
            local = jnp.clip(src - row0 + bm, 0, 2 * bm - 1)
            acc += jnp.where(lo_ok, both[local, d] * x[jnp.clip(src, 0,
                                                                n - 1)], 0.0)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("w", "bm", "interpret"))
def band_mv_pallas(band: jax.Array, x: jax.Array, w: int, bm: int = 256,
                   interpret: bool = True) -> jax.Array:
    """y = A x for symmetric band A ((n, w+1) storage); n % bm == 0, w <= bm."""
    n, wp1 = band.shape
    assert n % bm == 0 and w < bm and wp1 == w + 1

    return pl.pallas_call(
        functools.partial(_band_mv_kernel, w=w, bm=bm, n=n),
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, wp1), lambda i: (i, I0)),
            # previous tile (clamped at the first step; masked in-kernel)
            pl.BlockSpec((bm, wp1), lambda i: (jnp.maximum(i - 1, 0), I0)),
            pl.BlockSpec((n,), lambda i: (I0,)),
        ],
        out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), band.dtype),
        interpret=interpret,
    )(band, band, x)
