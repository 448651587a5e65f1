"""Jitted public wrapper for the band matvec kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import band_mv_pallas
from .ref import band_mv_ref, band_to_dense, dense_to_band


@functools.partial(jax.jit, static_argnames=("w", "bm", "force_interpret"))
def band_mv(band: jax.Array, x: jax.Array, w: int, bm: int = 128,
            force_interpret: bool | None = None) -> jax.Array:
    """y = A x for symmetric band A in (n, w+1) storage (zero-pads rows)."""
    n = band.shape[0]
    if not dispatch.use_pallas(band.dtype, force=True):
        return band_mv_ref(band, x)
    interpret = dispatch.interpret(force_interpret)
    bm_ = min(bm, n)
    while n % bm_:
        bm_ -= 1
    if w >= bm_:
        bm_ = n  # single tile fallback for tiny n
    pad = (-n) % bm_
    if pad:
        band = jnp.pad(band, ((0, pad), (0, 0)))
        x = jnp.pad(x, (0, pad))
    y = band_mv_pallas(band, x, w=w, bm=bm_, interpret=interpret)
    return y[:n]


__all__ = ["band_mv", "band_mv_ref", "band_to_dense", "dense_to_band"]
