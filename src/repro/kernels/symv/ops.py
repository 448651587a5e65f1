"""Jitted public wrappers for the one-triangle symv/symm kernel: padding
and the Pallas-or-XLA choice of ``kernels.dispatch``.

Off-TPU the kernel body executes in interpret mode — the Python-level
oracle of the TPU lowering. On a TPU, f32/bf16 operands compile the kernel
and f64 operands take the XLA product (Mosaic has no 64-bit types).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import symm_block_pallas
from .ref import symm_block_ref, symv_ref


@functools.partial(jax.jit, static_argnames=("block", "force_interpret"))
def symv(A: jax.Array, x: jax.Array, block: int = 256,
         force_interpret: bool | None = None) -> jax.Array:
    """y = A x for symmetric A via the one-triangle Pallas kernel: the
    one-column case of ``symm_block`` (a 2-D (n, 1) operand, whose layout
    the kernel and XLA agree on)."""
    return symm_block(A, x[:, None], block=block,
                      force_interpret=force_interpret)[:, 0]


def _pick_block(n: int, block: int, interpret: bool) -> int:
    """The pad-target heuristic: a block that is a multiple of the tile
    granularity g — 8 sublanes in interpret mode, 128 lanes on a real TPU
    (the kernel's (8, 128) MXU tiling) — clamped to about the granularity
    ceiling of n. The two modes want opposite objectives:

    * interpret: every tile is a Python-level kernel call, so keep the
      grid as coarse as the requested block allows (nb tiles) and round
      the per-tile size up to g — waste <= g*nb rows. n=300 -> 2 tiles of
      152, 304 padded.
    * compiled: grid steps are cheap, padded bytes are the cost — pick the
      g-multiple block (<= requested) minimizing the padded size, ties to
      the larger block. n=300 -> 3 tiles of 128, 384 padded.
    """
    g = 8 if interpret else 128
    if interpret:
        nb = -(-n // max(g, block))
        per = -(-n // nb)
        return max(g, -(-per // g) * g)
    k_max = max(1, min(block, -(-n // g) * g) // g)
    best_block, best_padded = g, -(-n // g) * g
    for k in range(2, k_max + 1):
        b = g * k
        padded = -(-n // b) * b
        if padded <= best_padded:  # ties -> larger block
            best_block, best_padded = b, padded
    return best_block


def _vmem_bytes(block: int, p: int, dtype) -> int:
    """Resident blocks of one grid step, double-buffered: the (block,
    block) tile, two (block, p) RHS slices and two accumulators."""
    return 2 * (block * block + 4 * block * p) * jnp.dtype(dtype).itemsize


@functools.partial(jax.jit, static_argnames=("block", "force_interpret"))
def symm_block(A: jax.Array, X: jax.Array, block: int = 256,
               force_interpret: bool | None = None) -> jax.Array:
    """Y = A X for symmetric A and an (n, p) RHS block via the one-triangle
    Pallas kernel — the block-Lanczos fused matvec (p SYMVs in one pass).

    Pads n up to a block multiple; on a real TPU the RHS count p is
    additionally padded up to the 128-lane granularity (interpret mode
    runs p as-is). Zero padding is exact for the product. Operands the
    kernel cannot take (``kernels.dispatch``) get the XLA product.
    """
    n = A.shape[0]
    p = X.shape[1]
    interpret = dispatch.interpret(force_interpret)
    blk = _pick_block(n, block, interpret)
    pad_p = 0 if interpret else (-p) % 128
    if not dispatch.use_pallas(A.dtype, _vmem_bytes(blk, p + pad_p, A.dtype),
                               force=True):
        return A @ X
    pad = (-n) % blk
    if pad or pad_p:
        A = jnp.pad(A, ((0, pad), (0, pad)))
        X = jnp.pad(X, ((0, pad), (0, pad_p)))
    Y = symm_block_pallas(A, X, block=blk, interpret=interpret)
    return Y[:n, :p]


__all__ = ["symv", "symm_block", "symv_ref", "symm_block_ref"]
