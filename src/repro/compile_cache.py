"""Where JAX's persistent compilation cache lives.

Entry points call :func:`configure` before their first compile.  A cache
directory given from outside (``JAX_COMPILATION_CACHE_DIR``) is JAX's to
use and nothing is set in code.  Otherwise the cache goes to a fixed
directory inside the checkout, ``<repo>/.jax_cache`` (gitignored): the
cache key includes the directory, so a path derived from a temp name, pid
or time would never hit again.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point the persistent compile cache at its place; returns the
    directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
