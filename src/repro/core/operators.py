"""Operator abstraction for the Krylov-subspace variants.

The paper's ARPACK reverse-communication interface becomes a small pytree
protocol: an operator is a NamedTuple of arrays plus `apply_op`, which the
Lanczos driver closes over. Variants:

  * ExplicitC  — KE: y = C w (one SYMV, 2 n^2 flops/iter)
  * SplitC     — KE with C held as exact int8 slices (``split_c``): the
    product of emulated f64 on a TPU, run on the integer matrix unit
  * ImplicitC  — KI: y = U^{-T}(A(U^{-1} w))  (TRSV + SYMV + TRSV, 4 n^2)

Each can route its SYMV through the Pallas kernel path (``use_kernel=True``
set by the driver) or plain jnp (XLA dot).
"""
from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .looped import matmul, solve_upper

#: int8 slices of C in its split form, and the magnitude bits of a slice
SPLIT_SLICES = 8
SPLIT_BITS = 7
#: int8 slices of each (n, p) block a split product splits: 84 bits, so
#: every entry within 2^-31 of its column's largest is held exactly
BLOCK_SLICES = 12
#: every power of two whose reciprocal is normal too: the scales
_POW2 = np.ldexp(1.0, np.arange(-1021, 1022))


class ExplicitC(NamedTuple):
    C: jax.Array


class SplitC(NamedTuple):
    """C as exact int8 slices, formed once a solve by ``split_c``.

    Row i of C is ``scale[i] * sum_k Q[i, k] 2^(-7(k+1))`` to within
    ``2^-56 scale[i]``: ``scale[i]`` is the least power of two above
    max|C[i, :]| and ``Q[:, k]`` holds the k-th 7 magnitude bits of every
    entry, with its sign. The 56 bits hold each entry within a factor 8
    of its row's largest exactly, and the rest to 2^-55 of that largest.
    Q takes 8 bytes an entry, as C does in f64. The slice index is Q's
    middle axis: a TPU tiles an int8 array by its two minor axes, which
    (8, n) fills without padding, so the device keeps Q in that order and
    the product reads it without a copy."""
    Q: jax.Array        # int8 (n, SPLIT_SLICES, n)
    scale: jax.Array    # (n,) powers of two, C's dtype


class ImplicitC(NamedTuple):
    A: jax.Array
    U: jax.Array


Operator = Union[ExplicitC, SplitC, ImplicitC]
OPERATORS = (ExplicitC, SplitC, ImplicitC)


def _pow2_above(amax: jax.Array):
    """(2^e, 2^-e) for the least power of two 2^e above each amax, by
    exact comparisons with a table (the TPU cannot bitcast an f64).
    ``amax`` below 2^1021 in magnitude; 0 gets 2^-1021."""
    k = jnp.sum(amax[..., None] >= _POW2, axis=-1)
    return jnp.take(_POW2, k), jnp.take(_POW2[::-1], k)


def _slices(x: jax.Array, count: int) -> jax.Array:
    """int8 (x.shape[0], count, *x.shape[1:]): the leading 7-bit digits
    of |x| < 1 with x's sign, each step exact in floating point."""
    out = []
    for _ in range(count):
        x = x * 2.0 ** SPLIT_BITS
        digit = jnp.trunc(x)
        out.append(digit.astype(jnp.int8))
        x = x - digit
    return jnp.stack(out, axis=1)


def split_c(C: jax.Array) -> SplitC:
    """The split form of C (see ``SplitC``), entries below 2^1021."""
    scale, inv = _pow2_above(jnp.max(jnp.abs(C), axis=1))
    return SplitC(_slices(C * inv[:, None], SPLIT_SLICES), scale)


def _split_matmul(op: SplitC, W: jax.Array) -> jax.Array:
    """C W for an (n, p) block. W is split like C, one scale a column,
    into BLOCK_SLICES slices, and ONE int8 product of every C slice with
    every W slice, accumulated in int32, reads C once. Each product is
    exact for n < 2^17 (7 + 7 bits a term, under 31 bits in all); the
    products are summed in f64 from the smallest weight up, and scaled by
    powers of two."""
    n, p = W.shape
    col_scale, col_inv = _pow2_above(jnp.max(jnp.abs(W), axis=0))
    P = _slices(W * col_inv, BLOCK_SLICES).reshape(n, BLOCK_SLICES * p)
    R = lax.dot_general(op.Q, P, (((2,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
    R = R.reshape(n, SPLIT_SLICES, BLOCK_SLICES, p).astype(op.scale.dtype)
    acc = None
    for t in range(SPLIT_SLICES + BLOCK_SLICES - 2, -1, -1):
        # the products of weight 2^(-7(t+2)): a sum exact in f64
        S = sum(R[:, i, t - i] for i in range(max(0, t - BLOCK_SLICES + 1),
                                              min(t, SPLIT_SLICES - 1) + 1))
        acc = S if acc is None else S + acc * 2.0 ** -SPLIT_BITS
    return (acc * op.scale[:, None]) * (col_scale * 2.0 ** (-2 * SPLIT_BITS))


def _symm(M: jax.Array, w: jax.Array, use_kernel: bool) -> jax.Array:
    """y = M w for a vector or an (n, p) block — the block Lanczos core
    feeds whole blocks through ONE fused multi-RHS product (SYMM/GEMM)
    instead of p SYMVs."""
    if use_kernel:
        from repro.kernels.symv import ops as symv_ops
        if w.ndim == 1:
            return symv_ops.symv(M, w)
        return symv_ops.symm_block(M, w)
    if M.dtype == jnp.bfloat16:
        # XLA fallback of the kernel's fp32-accumulating bf16 MXU path
        return jnp.matmul(M, w, preferred_element_type=jnp.float32) \
            .astype(M.dtype)
    return matmul(M, w)


def apply_op(op: Operator, w: jax.Array, use_kernel: bool = False) -> jax.Array:
    """One operator application; the hot loop of KE (KE1) / KI (KI1-KI3).

    ``w`` may be a vector (n,) or an (n, p) Lanczos block; every stage
    (SYMM and the triangular solves) handles the multi-RHS case natively.
    """
    if isinstance(op, ExplicitC):
        return _symm(op.C, w, use_kernel)
    if isinstance(op, SplitC):
        if w.ndim == 1:
            return _split_matmul(op, w[:, None])[:, 0]
        return _split_matmul(op, w)
    if isinstance(op, ImplicitC):
        # KI1: wbar = U^{-1} w
        wbar = solve_upper(op.U, w)
        # KI2: what = A wbar
        what = _symm(op.A, wbar, use_kernel)
        # KI3: z = U^{-T} what
        return solve_upper(op.U, what, trans=True)
    raise TypeError(f"unknown operator {type(op)}")


def op_dim(op: Operator) -> int:
    if isinstance(op, ExplicitC):
        return op.C.shape[0]
    if isinstance(op, SplitC):
        return op.scale.shape[0]
    return op.A.shape[0]


def op_dtype(op: Operator):
    """The working dtype of the operator's products."""
    if isinstance(op, ExplicitC):
        return op.C.dtype
    if isinstance(op, SplitC):
        return op.scale.dtype
    return op.A.dtype
