"""Where JAX keeps compiled programs between processes.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before any JAX
work; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed, in-checkout default (git-ignored): the cache key includes the
#: directory, so it must not move between runs
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  A set ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it
    itself); otherwise the cache lives at :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
