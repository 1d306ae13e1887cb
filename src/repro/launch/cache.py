"""Where JAX's persistent compile cache lives, for the entry points.

Library modules never touch the cache: only a process's entry point
(``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``, the
benchmark mains) calls ``use_compile_cache`` before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own config has
    already read it and nothing is changed. Otherwise the cache goes to
    ``<repo>/.jax_cache``: a fixed path, because the directory is part of
    what a later process must find again — never a tmp dir, pid or
    timestamp."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
