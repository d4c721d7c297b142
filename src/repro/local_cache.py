"""Caches the program keeps between runs, inside its own checkout.

Nothing here reads or writes the user's home or a temp directory: a
checkout carries its own caches under ``.cache/`` (listed in
``.gitignore``), so a kernel tile pick or a compiled program never depends
on what some other file on the machine happens to hold.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache"

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
    and nothing is set here.  Otherwise the cache goes to ``.cache/jax`` in
    the checkout: a fixed path, because the path is part of every entry's
    key.  Call it from an entry point's ``main``, never at import time."""
    env = os.environ.get(ENV_COMPILE_CACHE)
    if env:
        return env
    import jax

    path = str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
