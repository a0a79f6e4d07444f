"""Where JAX keeps its persistent compilation cache for this checkout.

The fused float64 exploration programs take minutes to compile for a
TPU, so every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the
examples) turns the persistent cache on before its first compile.  The
cache directory is part of what a cached program is found by, so it is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
  """Point JAX's persistent compilation cache at its fixed directory and
  return that directory.  Call before the process's first compile."""
  path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
  if path:
    return path
  import jax
  jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
  return CHECKOUT_CACHE_DIR
