"""Where JAX keeps its persistent compilation cache.

The cache directory is part of what a lookup matches, so a path that
moves between runs never hits.  ``JAX_COMPILATION_CACHE_DIR`` wins when
the environment sets it; otherwise every entry point uses the one fixed
directory ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The environment's cache directory, else the checkout's."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def use_compile_cache() -> str:
    """Point JAX (and every child process, through the environment) at
    :func:`compile_cache_dir`; returns the directory.  Call it before the
    first compile: JAX decides once per process whether the cache is on."""
    path = compile_cache_dir()
    os.environ[ENV_VAR] = path
    # A process that imported jax already (sitecustomize, a test runner)
    # read the environment before it was set; keep a parent that never
    # imported jax off it.
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
