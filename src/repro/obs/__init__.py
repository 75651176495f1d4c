"""Unified observability subsystem: metrics, µP health telemetry, tracing.

Three layers, shared by training, serving and the sweep engine (see
docs/observability.md for the metric catalog and interpretation guide):

  - :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
    Prometheus text exposition + JSON snapshots; also the single home of
    the benchmarks' percentile summaries.
  - :mod:`repro.obs.telemetry` — online µP health: the train step emits
    coord-check statistics as a fixed-shape traced aux pytree, drained into
    a host ring buffer; a width-exponent drift detector flags scales that
    depart the parametrization's prediction (Fig. 5 as a monitor).
  - :mod:`repro.obs.trace` — host-side span tracer (JSONL, monotonic
    clock) for request phases and sweep candidate lifecycles; its spans
    are also annotations on the ``jax.profiler`` trace.

Instrumentation is off by default everywhere, and never device-side for
serving: attaching a :class:`ServeObs` cannot change a traced program, so
the engines' zero-recompile contract (``compile_count() == 1``) holds with
observability fully enabled (asserted in tests/test_obs.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus,
    percentile_summary,
)
from repro.obs.telemetry import (
    DriftDetector,
    DriftReport,
    RingBuffer,
    TrainObs,
    coord_size,
    flatten_stats,
    loglog_slope,
    update_ratios,
)
from repro.obs.trace import Tracer, load_jsonl


@dataclasses.dataclass
class ServeObs:
    """Serving-side observability bundle: pass to ``Engine(obs=...)`` /
    ``DynamicEngine(obs=...)``.  Purely host-side — the engines record into
    it around their (already-synchronized) dispatches, so the single
    compiled program is untouched."""

    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry
    )
    tracer: Optional[Tracer] = None


__all__ = [
    "DEFAULT_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "parse_prometheus",
    "percentile_summary",
    "DriftDetector",
    "DriftReport",
    "RingBuffer",
    "TrainObs",
    "ServeObs",
    "coord_size",
    "flatten_stats",
    "loglog_slope",
    "update_ratios",
    "Tracer",
    "load_jsonl",
]
