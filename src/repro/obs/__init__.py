"""Observability layer: per-request trace spans + process metrics registry.

No dependencies on the rest of ``repro`` (or on jax) — runtime/serve/api
import from here, never the other way around.  See README.md in this
directory for the span taxonomy and metric naming convention.
"""
from repro.obs.export import JsonLinesReporter, chrome_trace, write_chrome_trace
from repro.obs.metrics import (LATENCY_BUCKETS_MS, OCCUPANCY_BUCKETS, Counter,
                               Gauge, Histogram, LabeledRegistry,
                               MetricsRegistry, default_registry, render_key)
from repro.obs.trace import (Span, Trace, annotate, current_trace,
                             maybe_activate, set_annotator, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "LabeledRegistry", "MetricsRegistry",
    "LATENCY_BUCKETS_MS", "OCCUPANCY_BUCKETS", "default_registry",
    "render_key", "Span", "Trace", "annotate", "current_trace",
    "maybe_activate", "set_annotator", "span",
    "JsonLinesReporter", "chrome_trace", "write_chrome_trace",
]
