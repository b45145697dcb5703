"""Persistent XLA compile cache: placed from outside, or at one fixed path.

The cache directory is part of every entry's key, so a directory that moves
(tempfile, pid, timestamp) never hits. Entry points call
`enable_compile_cache()` before their first compilation:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing is set in
  code, so whoever placed the directory keeps control of it.
- unset: `<checkout>/.jax_cache` (in .gitignore), the same path from any
  working directory.

Stdlib-only at import; `child_env()` lets a parent that must stay off JAX
(the launcher) hand the same placement to its workers.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")
# JAX skips programs that compiled in under a second by default; with that
# threshold a compile near it is written on one run and not the next, and
# the hundreds of small eager-op programs of a model's start-up recompile
# every time. Cache everything unless the environment says otherwise.
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def cache_dir() -> str:
    """The directory the compile cache lives in for this environment."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if not os.environ.get(_MIN_SECS_ENV):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()


def child_env() -> dict:
    """Environment entries that give a worker process this placement
    without the parent importing jax."""
    return {ENV: cache_dir(),
            _MIN_SECS_ENV: os.environ.get(_MIN_SECS_ENV, "0")}


def entry_count() -> int:
    """Number of cached executables (0 when the directory does not exist)."""
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0
