"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`place_compile_cache` first thing in ``main()``
(never at import, so importing the library changes no JAX setting):

  * with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing;
  * otherwise the cache goes to ``.jax_cache/`` at the root of the
    checkout — one fixed path, because the path is part of what makes a
    later process find the entries again (a temporary name, a process id
    or a time stamp would never hit).  The variable is exported too, so
    worker processes the entry point starts use the same directory.
"""
from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    # JAX reads the variable when it is imported: here, and in children
    os.environ[ENV] = DEFAULT_DIR
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
