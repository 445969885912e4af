"""Where JAX keeps its persistent compilation cache for this checkout.

Scripts that compile large programs (``chip_smoke.py``, the benchmarks)
call :func:`enable` once at start-up; importing ``repro`` never does.

- When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
  nothing is changed: the cache lives there and nowhere else.
- Otherwise the cache goes to :data:`DIRNAME` under the checkout root
  the caller names.  The path is fixed (never a temp name, a pid or the
  time): it is part of the cache's key, so a moving directory never
  hits.  The directory is listed in ``.gitignore``.
"""
from __future__ import annotations

import os

#: the cache directory's name under the checkout root
DIRNAME = ".jax_cache"


def enable(root: str) -> str:
    """Turn on JAX's persistent compilation cache for a script run from
    the checkout at ``root``; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
