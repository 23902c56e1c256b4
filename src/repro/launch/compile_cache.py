"""Persistent XLA compilation cache for the entry points that run on a chip.

JAX keeps compiled programs across processes in the directory named by
``JAX_COMPILATION_CACHE_DIR``, which it reads itself.  Where that variable
is unset, :func:`enable_compile_cache` points the cache at ``.jax_cache/``
in the repository root: a fixed path, because the path is part of every
entry's key and a directory that moves never hits.

Called from ``main()`` of each chip entry point, never at import time, so
tests and library users keep JAX's own default (no persistent cache).
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the fallback cache directory, ``<repo>/.jax_cache`` (gitignored)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
