"""Where JAX's persistent compilation cache lives, for every entry point.

``chip_smoke.py``, ``bench.py``, the profiler CLI and the examples call
:func:`enable_compile_cache` once, before their first compile.  The cache
key includes the directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself and this module
  sets nothing, so a machine that places the cache from outside keeps it
  there;
* otherwise a fixed directory inside the checkout (``<repo>/.jax_cache``,
  listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> Optional[str]:
    """The directory this process should set, or ``None`` when the
    environment already places the cache (``JAX_COMPILATION_CACHE_DIR``)."""
    if os.environ.get(ENV_VAR):
        return None
    return str(_CHECKOUT_CACHE)


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (no-op when the environment places it).  Returns the directory set."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
