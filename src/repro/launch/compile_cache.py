"""Persistent JAX compilation cache — recompile-free repeat runs.

The round pipeline makes compilation a non-event *within* a process
(power-of-two cohort buckets + the executor warm-up pass); this module
extends that across processes: with the cache on, XLA executables are
serialized to disk on first compile and deserialized on every later run
with the same dispatch signature.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when set, wins outright — JAX reads it
  itself and this module sets no directory of its own (an explicit
  ``path`` is ignored too);
* otherwise ``path`` (``ExperimentConfig.compilation_cache_dir``), or
  for the entry points (``chip_smoke.py``, ``python -m
  repro.launch.train``) the fixed in-checkout ``DEFAULT_CACHE_DIR``.

The path is part of the cache key's locality: it is never derived from a
temporary name, a pid or the time, so a re-run finds its entries.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/…)
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_enabled_dir: Optional[str] = None


def enable_compilation_cache(path: Optional[str] = None) -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``path``,
    else ``DEFAULT_CACHE_DIR``.

    The min-size/min-time floors are dropped to zero so the small
    kernels and group-train dispatches this repo compiles are all
    eligible — the defaults only persist "expensive" compiles.
    """
    global _enabled_dir
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        target = os.path.abspath(os.path.expanduser(env))
    else:
        target = os.path.abspath(os.path.expanduser(
            path or DEFAULT_CACHE_DIR))
    if _enabled_dir == target:
        return target
    if not env:
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _enabled_dir = target
    return target


def cache_dir() -> Optional[str]:
    """The active cache directory, or None when not enabled."""
    return _enabled_dir
