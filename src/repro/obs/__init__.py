"""repro.obs — the unified observability plane (ISSUE 10).

* :mod:`repro.obs.registry` — counters/gauges/streaming histograms with
  exact order-independent snapshot merges (safe across the worker RPC
  boundary).
* :mod:`repro.obs.trace` — request-scoped span records on the event
  stream; ``tools/tracelens.py`` turns them into timelines and Perfetto
  ``trace.json``.
* :mod:`repro.obs.compiles` — XLA compiles as ``compile`` spans on the
  attached tracers.
* :mod:`repro.obs.schema` — the closed-world registry of event kinds and
  span names (CI fails on undeclared kinds).
* :mod:`repro.obs.memstat` — planner-vs-live memory reconciliation.
"""
from repro.obs.memstat import MemStat
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                hist_quantile)
from repro.obs.schema import EVENT_KINDS, SPAN_NAMES
from repro.obs.trace import Tracer, maybe_span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "hist_quantile",
    "Tracer", "maybe_span", "MemStat", "EVENT_KINDS", "SPAN_NAMES",
]
