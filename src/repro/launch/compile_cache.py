"""Where entry points keep JAX's persistent compilation cache.

A full-width serving step compiles for tens of seconds; the persistent
cache lets the next process on the same machine load it instead.  The
cache key includes the directory, so the directory is fixed: the
deployment's ``JAX_COMPILATION_CACHE_DIR`` when it sets one, otherwise
``<repo root>/.jax_cache`` (listed in ``.gitignore``).

Only entry points call ``enable()``; importing ``repro`` turns no cache
on, so library callers and the tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; returns its path.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it at
    import and nothing is changed here."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
