"""Where JAX's persistent compilation cache lives.

The entry scripts (``chip_smoke.py``, ``bench.py``,
``__graft_entry__.py``) call :func:`enable_compile_cache` before their
first jit. The directory is part of the cache key's surroundings — a
directory that moves never hits — so it is placed from outside or fixed:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  nothing is set in code;
- unset: one fixed directory inside the checkout (``.gitignore`` lists
  it), the same for every process and every run.

The library never turns the cache on by itself, and neither do the
tests."""

from __future__ import annotations

import os

CACHE_DIR_NAME = ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    from ray_tpu.cluster.child_env import package_root

    path = os.path.join(package_root(), CACHE_DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
