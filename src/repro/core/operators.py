"""Operator abstraction for the Krylov-subspace variants.

The paper's ARPACK reverse-communication interface becomes a small pytree
protocol: an operator is a NamedTuple of arrays plus `apply_op`, which the
Lanczos driver closes over. Variants:

  * ExplicitC  — KE: y = C w (one SYMV, 2 n^2 flops/iter)
  * ImplicitC  — KI: y = U^{-T}(A(U^{-1} w))  (TRSV + SYMV + TRSV, 4 n^2)

Each can route its SYMV through the Pallas kernel path (``use_kernel=True``
set by the driver) or plain jnp (XLA dot).
"""
from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

from .looped import matmul, solve_upper


class ExplicitC(NamedTuple):
    C: jax.Array


class ImplicitC(NamedTuple):
    A: jax.Array
    U: jax.Array


Operator = Union[ExplicitC, ImplicitC]


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
    return op.A.shape[0]


def matvecs_per_apply(op: Operator) -> int:
    """Bookkeeping for the benchmark tables: flop-equivalent 2n^2 units."""
    return 1 if isinstance(op, ExplicitC) else 2
