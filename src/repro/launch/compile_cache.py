"""Persistent XLA compilation cache placement for the command-line entry points.

A cold start of the full-width serving path compiles the encode, every
prefill bucket and the decode scan; JAX's persistent compilation cache keeps
those executables across processes.  :func:`enable_compile_cache` is called
by `chip_smoke.py`, the `benchmarks/*` mains and `launch/train.py` — never at
import, so tests and library users keep JAX's own default (off).

Placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing here changes; otherwise the cache lives at one fixed
directory inside the checkout (``.jax_cache/``, git-ignored).  The path is
part of what a later run must match to hit, so it is never a temp, pid or
time-based directory.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
