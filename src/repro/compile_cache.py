"""Persistent XLA compilation cache for the repo's entry-point scripts.

Every k of the ladder and every shard_map round compiles its own program,
so a cold process spends minutes compiling.  `enable()` lets a second
process reuse what the first compiled.  Scripts call it once, before their
first compile; importing `repro` never calls it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in .gitignore): the path is part of what
a cache hit needs, so it must not move between runs.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
