"""Pallas TPU kernel: batched Givens rotation over row-pair tiles.

The TT2 bulge chase applies wavefronts of G independent Givens rotations:
each rotation mixes one row pair with its (c, s) coefficients. Dense-storage
code dispatches one masked full-row update per rotation; this kernel streams
a whole block of (c, s) pairs over row-pair tiles held in VMEM, so one
launch applies the entire wavefront (to the packed band windows and to the
transposed-Q row pairs alike).

Layout: the pair axis is split into two (G, L) operands (x0 = first rows,
x1 = second rows) so tiles are plain (bg, bl) VPU blocks — a (G, 2, L)
block would put the size-2 pair axis in the sublane dimension and waste
7/8 of each tile. (c, s) ride along as (G, 1) columns broadcast per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import I0



def _rot_apply_kernel(x0_ref, x1_ref, c_ref, s_ref, y0_ref, y1_ref):
    # bf16 tiles rotate in fp32 (VPU fma in the accumulator dtype) and
    # cast at the store; fp32/fp64 compute in kind
    wt = jnp.float32 if x0_ref.dtype == jnp.bfloat16 else x0_ref.dtype
    x0 = x0_ref[...].astype(wt)   # (bg, bl)
    x1 = x1_ref[...].astype(wt)
    c = c_ref[...].astype(wt)     # (bg, 1) -> broadcasts over the lane dim
    s = s_ref[...].astype(wt)
    y0_ref[...] = (c * x0 + s * x1).astype(y0_ref.dtype)
    y1_ref[...] = (-s * x0 + c * x1).astype(y1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bg", "bl", "interpret"))
def rot_apply_pallas(x0: jax.Array, x1: jax.Array, c: jax.Array,
                     s: jax.Array, bg: int = 8, bl: int = 128,
                     interpret: bool = True):
    """Rotate G row pairs: x0, x1 are (G, L); c, s are (G, 1).

    Requires G % bg == 0 and L % bl == 0 (the ops wrapper pads).
    Returns (y0, y1), both (G, L).
    """
    G, L = x0.shape
    assert G % bg == 0 and L % bl == 0, (G, L, bg, bl)
    grid = (G // bg, L // bl)
    return pl.pallas_call(
        _rot_apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg, bl), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bl), lambda i, j: (i, j)),
            pl.BlockSpec((bg, 1), lambda i, j: (i, I0)),
            pl.BlockSpec((bg, 1), lambda i, j: (i, I0)),
        ],
        out_specs=[
            pl.BlockSpec((bg, bl), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bl), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, L), x0.dtype),
            jax.ShapeDtypeStruct((G, L), x0.dtype),
        ],
        interpret=interpret,
    )(x0, x1, c, s)
