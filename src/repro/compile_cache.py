"""The one place that sets up JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.serve``,
``benchmarks/run.py``) call :func:`setup` before anything else uses JAX,
so a second cold run loads the drain and probe programs instead of
compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the directory itself,
  and no directory is set in code.
* Otherwise the cache lives at ``<checkout>/.jax_cache``.  The path is
  fixed (never built from a temporary name, a process id or the time):
  a directory that moves is never hit again.

Either way every program is cached, not only those over JAX's default
one-second compile threshold: on a TPU v5e each drain-bucket program
compiles in under a second, and a cold replay compiles dozens of them.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIRNAME", "setup"]

CACHE_DIRNAME = ".jax_cache"
_CHECKOUT = Path(__file__).resolve().parents[2]  # <checkout>/src/repro/


def setup() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
