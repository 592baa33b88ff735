"""Unified telemetry for the repro: metrics registry, span tracer, and
analog-health counters.

Three planes, one package:

  * :mod:`repro.obs.metrics` — counters/gauges/histograms with labels;
    JSON snapshot + Prometheus text exposition. The serving Scheduler's
    metrics are registry-backed.
  * :mod:`repro.obs.trace` — thread-safe ring-buffer span tracer
    (~zero cost disabled), Chrome-trace/Perfetto export, composes with
    ``jax.profiler`` via named annotations; ``op_scopes`` maps a compiled
    program's instructions to their ``jax.named_scope`` paths.
  * :mod:`repro.obs.health` — device-side accumulators for RRNS
    corrected/uncorrected residue faults and per-channel noise-stage
    activations, fetched with one host transfer per snapshot.

``repro.obs.http`` serves the first two over HTTP
(``launch/serve.py --metrics-port``).
"""

from . import health
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry)
from .trace import SpanTracer, configure, get_tracer, profile_window

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanTracer",
    "configure",
    "get_registry",
    "get_tracer",
    "health",
    "profile_window",
]
