"""Counts of XLA compilations in this process, from JAX's monitoring events:
a backend compile, or a program read back from the persistent cache."""
from __future__ import annotations

import collections

import jax

COUNTS: collections.Counter = collections.Counter()

_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


def _event(event: str, **_) -> None:
    if event == _HIT:
        COUNTS["cache_hits"] += 1


def _duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND:
        COUNTS["backend_compiles"] += 1


jax.monitoring.register_event_listener(_event)
jax.monitoring.register_event_duration_secs_listener(_duration)


def count() -> int:
    """Programs compiled or read from the cache so far."""
    return COUNTS["backend_compiles"] + COUNTS["cache_hits"]
