"""Production mesh construction.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the 512-device XLA flag is set only by dryrun.py, before
any jax import).
"""
from __future__ import annotations

from repro.dist.partitioning import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for in-CI multi-device tests (subprocesses set their own
    --xla_force_host_platform_device_count)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
