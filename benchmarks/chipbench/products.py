"""Matrix products at the operands' own precision, tiled for f64 on a TPU.

A copy of the program's ``core.looped.matmul_tiled`` arithmetic, kept
with the benchmark so that a change to the program cannot move the
yardstick. XLA emulates f64 on a v5e and keeps ~16x a product's output
and ~6.5x its larger input as temporaries, so an (n, n) f64 product at
n=10,240 would ask for ~13 GB of a 16 GB chip; forming it one
(TILE, TILE) output tile per ``fori_loop`` step sizes the temporaries by
the tile. Every product runs at ``Precision.HIGHEST``: on a TPU the
default lets f32 operands take bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: output tile edge of ``matmul_tiled``
TILE = 1024
HIGHEST = lax.Precision.HIGHEST


def dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=HIGHEST)


def _pad2(M: jax.Array, r: int, c: int) -> jax.Array:
    if (r, c) == M.shape:
        return M
    return jnp.pad(M, ((0, r - M.shape[0]), (0, c - M.shape[1])))


def matmul_tiled(a: jax.Array, b: jax.Array, tile: int = TILE) -> jax.Array:
    """``a @ b``, one output tile per step of a single ``fori_loop``."""
    m, k = a.shape
    n = b.shape[1]
    tm, tn = min(tile, m), min(tile, n)
    nm, nn = -(-m // tm), -(-n // tn)
    a_p = _pad2(a, nm * tm, k)
    b_p = _pad2(b, k, nn * tn)
    out = jnp.zeros((nm * tm, nn * tn), jnp.result_type(a, b))

    def step(t, out):
        r0 = (t // nn) * tm
        c0 = (t % nn) * tn
        ai = lax.dynamic_slice(a_p, (r0, 0), (tm, k))
        bj = lax.dynamic_slice(b_p, (0, c0), (k, tn))
        return lax.dynamic_update_slice(out, dot(ai, bj), (r0, c0))

    return lax.fori_loop(0, nm * nn, step, out)[:m, :n]


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at HIGHEST precision; an f64 product whose output spans
    more than one tile is formed tile by tile (on any platform: the
    arithmetic is the same, only the temporaries differ)."""
    if (max(a.shape[0], b.shape[1]) > TILE
            and jnp.result_type(a, b) == jnp.float64):
        return matmul_tiled(a, b)
    return dot(a, b)
