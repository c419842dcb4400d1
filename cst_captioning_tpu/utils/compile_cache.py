"""Where JAX's persistent compilation cache lives.

Compiling is a large part of a cold run on the chip (the RL decode and
update programs take tens of seconds each), and every process start pays it
again unless executables persist. One rule, applied by every entry point
(``cli.train``, ``cli.eval``, ``chip_smoke.py``) before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself, and
  nothing is set in code — whoever runs the program places the cache.
- unset: ``<checkout>/.jax_cache`` (git-ignored). Fixed, never a temporary
  name, a pid or a time: a directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
