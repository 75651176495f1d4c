"""JAX's persistent compilation cache, at a place that survives the run.

``enable()`` is called by every command-line entry point (train, serve,
sweep, ``benchmarks/run.py``, ``chip_smoke.py``) before its first JAX
computation:

- when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory from
  the environment itself and no other is set here;
- otherwise the cache lives at ``.jax_cache/`` in the root of the checkout
  (git-ignored).  The path is fixed, never a temporary name, a pid or a
  time: a cache whose directory moves is never hit.

``EVENTS`` counts cache hits and misses in this process (JAX's monitoring
events), so a run can report whether its compiles came from the cache.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

EVENTS: collections.Counter = collections.Counter()


def _count(event: str, **_) -> None:
    if event == _HIT:
        EVENTS["hits"] += 1
    elif event == _MISS:
        EVENTS["misses"] += 1


jax.monitoring.register_event_listener(_count)


def enable() -> str:
    """Turn the persistent cache on (idempotent); returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick to compile: a cold call on a fresh
    # machine pays for all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
