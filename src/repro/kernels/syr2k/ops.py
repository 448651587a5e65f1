"""Jitted public wrapper for the fused SYR2K kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import syr2k_pallas
from .ref import syr2k_ref


def _round_up(x: int, b: int) -> int:
    return -(-x // b) * b


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "force_interpret"))
def syr2k(C: jax.Array, V: jax.Array, W: jax.Array, alpha: float = -1.0,
          bm: int = 256, force_interpret: bool | None = None) -> jax.Array:
    """C + alpha (V W^T + W V^T), padding n to the tile size."""
    n, k = V.shape
    if not dispatch.use_pallas(C.dtype, force=True):
        return syr2k_ref(C, V, W, alpha)
    interpret = dispatch.interpret(force_interpret)
    bm_ = min(bm, _round_up(n, 8))
    np_ = _round_up(n, bm_)
    pad = np_ - n
    if pad:
        C = jnp.pad(C, ((0, pad), (0, pad)))
        V = jnp.pad(V, ((0, pad), (0, 0)))
        W = jnp.pad(W, ((0, pad), (0, 0)))
    out = syr2k_pallas(C, V, W, alpha=alpha, bm=bm_, interpret=interpret)
    return out[:n, :n]


__all__ = ["syr2k", "syr2k_ref"]
