"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` first thing in ``main``; the
library never does so on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; this module sets
  no other directory.
* Otherwise: one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (gitignored).  The path is part of the cache key, so it must not move
  between runs: never a temp, pid or time-based path.

Every compile is cached (minimum compile time 0 s), so a second run of the
same programs loads them instead of compiling.  :func:`stats` counts the
cache's hits and misses in this process.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_):
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    global _listening
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def stats() -> Dict[str, int]:
    """Persistent-cache hits and misses counted since :func:`enable`."""
    return dict(_counts)
