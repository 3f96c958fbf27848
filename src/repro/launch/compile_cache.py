"""Where JAX's persistent compilation cache lives.

The launchers' ``main()`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, never at import.
``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads the
variable itself and nothing is set in code.  Otherwise the cache goes to a
fixed ``.jax_cache/`` at the repository root (ignored by git): the cache
directory is part of an entry's key, so a path that moved between runs
would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
