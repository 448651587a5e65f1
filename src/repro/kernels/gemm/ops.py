"""Jitted public wrapper for the GEMM kernel: padding + dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import gemm_pallas
from .ref import gemm_ref


def _round_up(x: int, b: int) -> int:
    return -(-x // b) * b


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "force_interpret"))
def gemm(A: jax.Array, B: jax.Array, bm: int = 128, bn: int = 128,
         bk: int = 128, force_interpret: bool | None = None) -> jax.Array:
    """C = A @ B via the tiled Pallas kernel (zero-pads to tile multiples);
    operands the kernel cannot take (``kernels.dispatch``) get XLA's."""
    m, k = A.shape
    _, n = B.shape
    if not dispatch.use_pallas(A.dtype, force=True):
        return gemm_ref(A, B)
    interpret = dispatch.interpret(force_interpret)
    bm_, bn_, bk_ = min(bm, _round_up(m, 8)), min(bn, _round_up(n, 8)), \
        min(bk, _round_up(k, 8))
    mp, np_, kp = _round_up(m, bm_), _round_up(n, bn_), _round_up(k, bk_)
    Ap = jnp.pad(A, ((0, mp - m), (0, kp - k)))
    Bp = jnp.pad(B, ((0, kp - k), (0, np_ - n)))
    C = gemm_pallas(Ap, Bp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return C[:m, :n]


__all__ = ["gemm", "gemm_ref"]
