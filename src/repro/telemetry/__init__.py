"""Telemetry: per-module tracing + typed metrics feeding the dispatcher.

Hetis's online load-dispatching policy rebalances Attention head placement
from live latency/memory signals; this package provides those signals:

  * :class:`Tracer` — nested spans (wall-clock, each also a profiler
    annotation ``engine.<name>``, or explicit simulated timelines),
    ring-buffered, exportable as Chrome ``trace_event`` JSON.
  * :class:`MetricsRegistry` — counters / gauges / histograms with lazy
    percentiles and EWMA smoothing; ``snapshot()`` feeds the dispatcher,
    hauler, and cost model with *measured* values.
  * :func:`count_recompiles` — wraps jitted callables with a recompile
    counter so bucketing regressions trip metrics, not just tests.

See ``docs/observability.md``.
"""

from repro.telemetry.export import (spans_to_chrome, validate_chrome_trace,
                                    validate_file)
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, MetricsView,
                                     count_recompiles)
from repro.telemetry.tracer import Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricsView",
    "Span", "Tracer", "count_recompiles", "spans_to_chrome",
    "validate_chrome_trace", "validate_file",
]
