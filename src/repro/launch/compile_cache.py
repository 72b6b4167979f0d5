"""Where JAX keeps its persistent compile cache.

A TPU compile of a sort graph takes minutes, so every entry point that
touches the device calls :func:`enable` before its first compile.  The
directory is

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — then that directory
  and no other;
* otherwise ``<checkout>/.jax_cache``: a fixed absolute path inside the
  checkout (git-ignored), the same from any working directory, because
  the cache only hits when the path stays put.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def cache_dir() -> str:
    """The cache directory this process should use."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Point JAX's persistent compile cache at :func:`cache_dir`; call
    before the first compile.  Returns the directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
