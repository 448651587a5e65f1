"""Public wrapper for the fused compact-WY panel factorization.

``house_panel`` is the stage-1 panel unit of the band reduction: a whole
(rows, b) panel goes to compact-WY form (V, T) in ONE device operation. On
TPU it lowers to the Pallas kernel (panel resident in VMEM, reflector loop
unrolled); elsewhere it falls back to the identical pure-jnp expression, so
the panel sweep stays a single traceable program on every backend —
including inside ``lax.fori_loop`` bodies (``row_start`` may be traced),
under ``vmap`` in ``core.batched``, and inside the ``shard_map``-ped
distributed sweep of ``dist.sharded_la``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from .kernel import house_panel_pallas
from .ref import house_panel_ref


def house_panel(E: jax.Array, row_start,
                force_kernel: bool = False,
                force_interpret: bool | None = None):
    """Compact-WY factorization of E[row_start:, :] — returns (V, T).

    E: (rows, b) full-height panel; reflector j pivots at row
    ``row_start + j`` (traced ok) and rows above pass through untouched.
    V is (rows, b) with zeros above each pivot, T is (b, b) upper
    triangular; Q = I - V T V^T. Pivots past the panel end (the rows < b
    tail panel) yield identity reflectors (tau = 0).

    Dispatches (``kernels.dispatch``) to the Pallas kernel on TPU when the
    dtype and the VMEM-resident panel allow it (or when
    ``force_kernel=True``, using interpret mode off-TPU); otherwise the
    pure-jnp oracle. Rows are padded to the sublane multiple internally.
    """
    if not dispatch.use_pallas(E.dtype, vmem_bytes(*E.shape),
                               force=force_kernel):
        if E.dtype == jnp.bfloat16:
            # mirror the kernel's fp32-accumulating bf16 path: reflector
            # norms/taus cancel too hard for bf16 arithmetic
            V, T = house_panel_ref(E.astype(jnp.float32), row_start)
            return V.astype(E.dtype), T.astype(E.dtype)
        return house_panel_ref(E, row_start)
    rows, b = E.shape
    pad = (-rows) % 8
    if pad:
        E = jnp.pad(E, ((0, pad), (0, 0)))
    interpret = dispatch.interpret(force_interpret)
    rs = jnp.asarray(row_start, jnp.int32).reshape((1,))
    V, T = house_panel_pallas(E, rs, interpret=interpret)
    return V[:rows], T


def vmem_bytes(rows: int, b: int) -> int:
    """The kernel keeps the whole panel resident, and its reflector loop
    is unrolled: about b + 4 live (rows, b) f32 values, each padded to the
    128-lane tile. (Compiled for a v5e: a (4096, 16) panel asks for 40 MB
    of the 16 MiB scoped VMEM and is refused; (2048, 16) compiles.)"""
    return (b + 4) * (-(-rows // 8) * 8) * max(b, 128) * 4


__all__ = ["house_panel", "house_panel_ref", "vmem_bytes"]
