"""Where JAX's persistent compilation cache lives.

The directory is part of every cache key, so it must not move between
runs: a name made from a pid, a time or ``tempfile`` never hits. It is
placed from outside when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads
that variable itself, and nothing in this repo sets another directory —
and otherwise falls to one fixed git-ignored directory inside the
checkout. Processes started afterwards (GCS, raylet, and through the
raylet's spawn environment every worker) inherit the variable.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Default ``JAX_COMPILATION_CACHE_DIR`` to the checkout's cache
    directory and return the directory in force. Call before the process
    first imports jax: the variable is read at import."""
    return os.environ.setdefault(_ENV, _DEFAULT_DIR)
