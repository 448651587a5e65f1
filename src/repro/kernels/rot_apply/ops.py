"""Public wrapper for the batched Givens rotation kernel.

``rot_apply`` is the wavefront unit of the TT2 bulge chase: G independent
rotations applied to G row pairs as ONE fused update. On TPU it lowers to
the Pallas kernel (row-pair tiles streamed through VMEM); elsewhere it
falls back to the identical vectorized XLA expression, so the bulge chase
stays a single traceable program on every backend (including under vmap in
``core.batched``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import rot_apply_pallas
from .ref import rot_apply_ref


def rot_apply(pairs: jax.Array, cs: jax.Array,
              force_kernel: bool = False,
              force_interpret: bool | None = None) -> jax.Array:
    """Apply G independent Givens rotations to G row pairs.

    pairs: (G, 2, L) — G disjoint row pairs.
    cs:    (G, 2)    — (c, s) per pair, out0 = c*x0 + s*x1, out1 = -s*x0 + c*x1.

    Dispatches (``kernels.dispatch``) to the Pallas kernel on TPU for the
    dtypes it takes (or when ``force_kernel=True``, using interpret mode
    off-TPU); otherwise the vectorized jnp fallback. Shapes are padded to
    tile multiples internally.
    """
    if not dispatch.use_pallas(pairs.dtype, force=force_kernel):
        if pairs.dtype == jnp.bfloat16:
            # fp32-accumulate the rotation (the kernel's bf16 path does
            # the same); the store casts back to bf16
            out = rot_apply_ref(pairs.astype(jnp.float32),
                                cs.astype(jnp.float32))
            return out.astype(pairs.dtype)
        return rot_apply_ref(pairs, cs)
    G, _, L = pairs.shape
    bg = 8 if G >= 8 else max(G, 1)
    bl = 128 if L >= 128 else L
    gpad = (-G) % bg
    lpad = (-L) % bl
    x0 = pairs[:, 0, :]
    x1 = pairs[:, 1, :]
    c = cs[:, 0:1]
    s = cs[:, 1:2]
    if gpad or lpad:
        x0 = jnp.pad(x0, ((0, gpad), (0, lpad)))
        x1 = jnp.pad(x1, ((0, gpad), (0, lpad)))
        c = jnp.pad(c, ((0, gpad), (0, 0)), constant_values=1.0)
        s = jnp.pad(s, ((0, gpad), (0, 0)))
    interpret = dispatch.interpret(force_interpret)
    y0, y1 = rot_apply_pallas(x0, x1, c, s, bg=bg, bl=bl, interpret=interpret)
    return jnp.stack([y0[:G, :L], y1[:G, :L]], axis=1)


__all__ = ["rot_apply", "rot_apply_ref"]
