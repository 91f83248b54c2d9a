"""Start-of-run setup shared by the entry points: compile cache and device.

Called from each entry point's ``main()``, never at import: tests and
worker processes import these modules.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at ``<repo>/.jax_cache``:
    the directory is part of each entry's key, so it must not move between
    runs.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_line() -> str:
    """Which devices this process runs on, as JAX reports them.  JAX falls
    back to the CPU when an accelerator fails to initialise, so every entry
    point prints this first."""
    devs = jax.devices()
    return (f"device: platform={devs[0].platform} "
            f"kind={devs[0].device_kind} count={len(devs)}")
