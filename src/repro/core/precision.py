"""Precision policy for the mixed-precision solver pipeline.

Three levels, threaded as ``precision=`` through ``gsyeig.solve``,
``core.batched.solve_batched`` and the distributed drivers:

  ``fp64``  — every stage in float64 (the default; identical to before)
  ``mixed`` — GEMM-heavy stages in float32
  ``fast``  — GEMM-heavy stages in bfloat16 with float32 accumulation

Only the GEMM-heavy stages demote (the TT1 panel sweep + SYR2K trailing
updates, the TT2 rotation wavefront, the TT4 back-transform, the KE/KI
fused matvec, and the TD reflector stages); Cholesky/standard form, the
tridiagonal eigensolve and all convergence/residual math stay float64,
and ``core.refinement`` restores fp64 accuracy of the returned
eigenpairs against the original pencil — the ELPA2-GPU / hybrid-solver
split (arXiv:2002.10991, arXiv:1207.1773).

The demotions each level is allowed to introduce are *declared* here
(``declared_downcasts``) so the static auditor can enforce them as a
policy instead of exempting the mixed pipeline from its dtype lint.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

PRECISIONS = ("fp64", "mixed", "fast")

_COMPUTE = {"fp64": jnp.float64, "mixed": jnp.float32, "fast": jnp.bfloat16}
# bf16 MXU paths accumulate in fp32; fp32 and fp64 accumulate in kind
_ACC = {"fp64": jnp.float64, "mixed": jnp.float32, "fast": jnp.float32}

# the exact convert_element_type edges each level may introduce — the
# static auditor's per-contract dtype policy (anything else is a leak)
_DECLARED = {
    "fp64": (),
    "mixed": ("float64->float32",),
    "fast": ("float64->bfloat16", "float64->float32"),
}


def exact_matmuls(fn):
    """Run ``fn`` with every matmul at its operands' own precision
    (``jax.default_matmul_precision("highest")``).

    On a TPU the default lets f32 products take bf16 passes; on a v5e an
    orthogonal factor built and checked with the tiled f64 products of
    ``core.looped`` at the default read max|Q^T Q - I| ~ 2.5e-6, where a
    plain f64 dot was exact. The public entry points carry this
    decorator, so the ``precision=`` axis is set by dtypes alone. On a CPU
    host f32 and f64 dots are exact either way."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def validate_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}")
    return precision


def compute_dtype(precision: str):
    """Storage/compute dtype of the demoted GEMM-heavy stages."""
    return _COMPUTE[validate_precision(precision)]


def acc_dtype(precision: str):
    """Accumulation dtype for reduced-precision contractions."""
    return _ACC[validate_precision(precision)]


def compute_eps(precision: str) -> float:
    return float(jnp.finfo(compute_dtype(precision)).eps)


def declared_downcasts(precision: str) -> Tuple[str, ...]:
    return _DECLARED[validate_precision(precision)]


def default_refine_steps(precision: str) -> int:
    """Fixed refinement step count for the traceable (batched) pipelines.

    Sized for the slowest workload in the benchmark matrix (the MD-like
    log spectrum at n=256, whose wanted-end relative gaps contract
    ~0.1-0.2x per sweep): enough sweeps to land BELOW the 1e-12 Table-3
    tolerances from fp32 (resp. bf16) pipeline output with an order of
    margin (BENCH_mixed measured worst 4e-14 / 2e-14 at these counts).
    Each sweep is O(n^2 (s + guard)) — cheap next to the O(n^3) pipeline
    it refines."""
    return {"fp64": 0, "mixed": 8, "fast": 16}[validate_precision(precision)]


def demote(x, precision: str):
    """Cast an array (or pytree of arrays) to the compute dtype."""
    dt = compute_dtype(precision)
    return jax.tree_util.tree_map(lambda a: a.astype(dt), x)


def promote(x, dtype=jnp.float64):
    """Cast an array (or pytree of arrays) back to the working dtype."""
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), x)


def ensure_strong(x, dtype=jnp.float64):
    """Promote a weak-typed (Python-scalar-born) input to the working dtype.

    ``jnp.full((n, n), 0.5)`` and friends carry ``weak_type=True``, which
    the auditor reports (``weak_type_inputs``) because it lets the first
    downstream op silently decide the precision. Strongly-typed inputs
    pass through untouched, whatever their dtype.
    """
    x = jnp.asarray(x)
    if getattr(x, "weak_type", False) or not jnp.issubdtype(
            x.dtype, jnp.floating):
        x = jax.lax.convert_element_type(x, dtype)
    return x


def matmul_acc(a, b):
    """``a @ b`` with fp32 accumulation for sub-fp32 operands.

    The XLA-fallback counterpart of the Pallas kernels' bf16 MXU paths:
    ``preferred_element_type`` pins the accumulator, the result is cast
    back to the operand dtype.
    """
    if a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16:
        out = jnp.matmul(a, b, preferred_element_type=jnp.float32)
        return out.astype(a.dtype)
    return a @ b
