"""Pallas TPU kernel: fused symmetric rank-2k update C += alpha(V W^T + W V^T).

The trailing update of blocked Householder tridiagonalization (TD1) and the
SYR2K step of blocked DSYGST (GS2). Fusing the two outer products means each
C tile makes exactly one HBM round trip per update instead of two — on TPU
the update is bandwidth-bound (2k flops per element at small k), so this
halves its roofline time.

Grid (i, j) over C tiles; V/W panels are (bm, k) with k = panel width (<= 128
in practice — a single MXU face), staying resident per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import I0


def _syr2k_kernel(c_ref, vi_ref, wj_ref, wi_ref, vj_ref, o_ref, *, alpha,
                  acc_dtype):
    # sub-fp32 operands accumulate in fp32 on the MXU (acc_dtype pins the
    # accumulator); the store casts back to the storage dtype
    contrib = jnp.dot(vi_ref[...], wj_ref[...].T,
                      preferred_element_type=acc_dtype)
    contrib += jnp.dot(wi_ref[...], vj_ref[...].T,
                       preferred_element_type=acc_dtype)
    acc = c_ref[...].astype(acc_dtype) + alpha * contrib
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "alpha", "interpret"))
def syr2k_pallas(C: jax.Array, V: jax.Array, W: jax.Array,
                 alpha: float = -1.0, bm: int = 256,
                 interpret: bool = True) -> jax.Array:
    """C + alpha (V W^T + W V^T); n % bm == 0 (ops.py pads), k arbitrary.

    bf16 operands take the fp32-accumulating MXU path (result cast back to
    bf16 at the store); fp32/fp64 accumulate in kind.
    """
    n, k = V.shape
    assert C.shape == (n, n) and W.shape == (n, k) and n % bm == 0
    acc_dtype = jnp.float32 if C.dtype == jnp.bfloat16 else C.dtype
    nb = n // bm
    return pl.pallas_call(
        functools.partial(_syr2k_kernel, alpha=alpha, acc_dtype=acc_dtype),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((bm, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bm, k), lambda i, j: (i, I0)),
            pl.BlockSpec((bm, k), lambda i, j: (j, I0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, I0)),
            pl.BlockSpec((bm, k), lambda i, j: (j, I0)),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), C.dtype),
        interpret=interpret,
    )(C, V, W, W, V)
