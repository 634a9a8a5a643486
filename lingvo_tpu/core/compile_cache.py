"""Where JAX's persistent compilation cache lives for this checkout.

The cache key includes the directory, so an entry is only found again at the
same path: the path must be fixed, never derived from a temp dir, a pid or the
clock. Every entry point (trainer CLI, chip_smoke, the benchmark, sweep tools,
tests) calls `Configure()` once before its first compile, so they all share
one cache and a second run of any of them starts warm.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def Configure() -> str:
  """Turns the persistent compilation cache on; returns its directory.

  With `JAX_COMPILATION_CACHE_DIR` set, JAX reads the variable itself: the
  cache is placed from outside and this sets no other directory. Without it
  the cache goes to `<checkout>/.jax_cache` (listed in `.gitignore`).
  """
  placed = os.environ.get(_ENV)
  if placed:
    return placed
  path = os.path.join(_CHECKOUT, ".jax_cache")
  jax.config.update("jax_compilation_cache_dir", path)
  return path
