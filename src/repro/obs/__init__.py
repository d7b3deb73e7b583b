"""Zero-dependency tracing + metrics for the AMIH serving stack.

Three stdlib-only modules (numpy never enters the picture, so fork
children and spawned cluster workers can import this package without
dragging jax in):

  - ``trace``   — monotonic-clock spans with thread-local nesting, a
                  sampling knob, and a cheap no-op path when disabled.
  - ``metrics`` — a process-wide registry of counters and bounded
                  histograms with percentile snapshots: kernel launches
                  and traces, device-to-host bytes, batches served, the
                  probing cache stats, and the serving ``LatencyTracker``.
  - ``export``  — Chrome trace-event JSON (Perfetto-loadable) plus a
                  JSONL metrics dump; ``python -m repro.obs.report``
                  summarizes a trace file into a per-stage breakdown.

Tracing is OFF by default: every instrumentation site checks one
attribute (``Tracer.enabled``) and falls through. ``Tracer.span`` also
writes into a JAX profiler trace whenever one records, tracer enabled
or not. Spans observe, never reorder — enabling tracing cannot change
search results.
"""

from .metrics import Counter, Histogram, MetricsRegistry, REGISTRY
from .trace import (
    NOOP_SPAN,
    Tracer,
    current,
    disable,
    enable,
    now_us,
    set_tracer,
)

__all__ = [
    "Counter",
    "Histogram",
    "MetricsRegistry",
    "NOOP_SPAN",
    "REGISTRY",
    "Tracer",
    "current",
    "disable",
    "enable",
    "now_us",
    "set_tracer",
]
