"""Where JAX keeps its persistent compilation cache.

A serving run compiles one program per jit bucket (k_cold × MoE caps × row,
length and page buckets), so a cold process can spend most of its time
compiling. The persistent cache lets the next process on the same machine
load those programs instead. Call ``use_compile_cache`` from an entry
point's ``main``, before the first compile; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout and listed in .gitignore: a directory that
# moves between runs never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already reads, and
    it is left alone; otherwise the cache lives in ``CHECKOUT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
