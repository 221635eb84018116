"""Persistent XLA compilation cache for the entry points.

Compiling olmo-1b's prefill and decode programs and the dump kernels costs
a large share of a cold run on the chip.  JAX's persistent cache keys on
the cache path too, so the cache only hits where the path is stable:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing else is
  configured here.
* unset — ``<checkout>/.jax_cache`` (listed in ``.gitignore``), the same
  directory on every run from the same checkout.

Entry points (``chip_smoke.py``, the benchmark scripts) call
:func:`enable_compile_cache` before their first compile.  Library imports
never do: a test process must not write a cache it cannot read back.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
