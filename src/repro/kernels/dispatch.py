"""Which path runs a call: the Pallas kernel or the XLA expression.

One decision for every kernel wrapper, made per call from what the call
can observe — the platform, the operand dtype and the block working set —
and never by catching a refused compile:

* Mosaic (the TPU kernel compiler) has no 64-bit vector types, so f64
  operands always take the XLA expression (XLA emulates f64 on a v5e).
* A kernel whose resident blocks exceed ``VMEM_BUDGET`` cannot be placed
  in the core's fast memory; those calls take the XLA expression too.
* Off-TPU the XLA expression is the default; ``force=True`` (the parity
  tests) runs the kernel body in interpret mode instead.

No kernel runs in interpret mode on a TPU: ``interpret()`` is False there
whatever the caller asks for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: dtypes Mosaic compiles for the kernels of this package
PALLAS_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))

#: bytes of resident kernel blocks a call may hold: the default scoped
#: VMEM limit of a TPU v5e core (callers count double buffering)
VMEM_BUDGET = 16 * 2**20

#: the int32 zero for BlockSpec index maps: a Python 0 traces as int64
#: under ``jax_enable_x64``, and Mosaic refuses an index map that returns
#: (i32, i64)
I0 = np.int32(0)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_ok(dtype, vmem_bytes: int = 0) -> bool:
    """True when Mosaic can compile a kernel over ``dtype`` operands whose
    resident blocks take ``vmem_bytes``."""
    return jnp.dtype(dtype) in PALLAS_DTYPES and vmem_bytes <= VMEM_BUDGET


def use_pallas(dtype, vmem_bytes: int = 0, force: bool = False) -> bool:
    """The per-call choice: the compiled kernel on a TPU when
    ``pallas_ok``; off-TPU only when ``force`` (interpret mode)."""
    if on_tpu():
        return pallas_ok(dtype, vmem_bytes)
    return force


def interpret(force_interpret: bool | None = None) -> bool:
    """Interpret mode for a kernel launch: never on a TPU; off-TPU unless
    the caller passes ``force_interpret=False`` (a compile for a
    described chip)."""
    if on_tpu():
        return False
    return True if force_interpret is None else bool(force_interpret)


__all__ = ["PALLAS_DTYPES", "VMEM_BUDGET", "I0", "on_tpu", "pallas_ok",
           "use_pallas", "interpret"]
