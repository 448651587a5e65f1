"""Process set-up shared by ``chip_smoke.py`` and the ``launch`` CLIs.

Nothing here runs when the library is imported; each entry point calls
these itself, before its first JAX computation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: where the persistent compile cache lives unless
#: ``JAX_COMPILATION_CACHE_DIR`` says otherwise: a fixed path inside the
#: checkout (the path is part of the cache key, so it must not move)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and no other directory is set; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def force_host_devices(n: int | None) -> None:
    """``--devices N``: N host-platform devices, for testing a mesh on a
    CPU host. Must run before JAX initializes a backend. On any other
    platform it refuses rather than lay a mesh over host devices."""
    if not n:
        return
    jax.config.update("jax_num_cpu_devices", int(n))
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"--devices forces host CPU devices for testing, but the "
            f"default backend is {jax.default_backend()}; drop --devices "
            f"to lay the mesh over the {len(jax.devices())} "
            f"{jax.devices()[0].platform} device(s)")


__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache", "force_host_devices"]
