"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py`` and the ``launch.train`` / ``launch.serve``
mains) call :func:`use_compile_cache` once before their first compile;
importing a module never changes the cache.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

# the checkout root: src/repro/launch/compile_cache.py -> three levels up
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_ROOT, ".jax_cache")


def use_compile_cache(n_chips: int = 1) -> Optional[str]:
    """Keep the compile cache where ``JAX_COMPILATION_CACHE_DIR`` says (JAX
    reads that variable itself, so nothing is set), else in ``.jax_cache/``
    at the checkout root: a fixed path, since the path is part of the
    cache key. Returns the directory in use.

    A process that runs programs over more than one chip (``n_chips`` > 1)
    compiles every program afresh and returns None: on a four-chip TPU v5e
    host, a 2-chip program read back from the cache for chips 2 and 3
    halted both chips ("Core halted unexpectedly"), where the same program
    compiled in the process ran."""
    if n_chips > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
