"""Where JAX keeps its persistent compilation cache.

The entry points (serve and train CLIs, ``chip_smoke.py``) call
:func:`use_compile_cache` once, before their first compile.  A directory
named by ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left as
it is.  Otherwise an accelerator's programs are cached at one fixed path
inside the checkout (``.jax_cache/``, gitignored): the path is part of the
cache key, so it never carries a temp name, a PID or a time.  On the CPU
backend no directory is set: XLA:CPU entries record the host's machine
features and warn each time they are read back.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Enable the persistent compile cache; returns its directory, or None
    where none is used."""
    import jax

    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
