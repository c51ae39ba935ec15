"""Serving-path observability: metrics registry, trace spans, exporters.

  metrics   process-global MetricsRegistry (counters/gauges/histograms),
            a backend-compile counter, and debug-only jit recording via
            jax.debug.callback
  trace     span() context manager with per-thread parent nesting, also
            written to the JAX profiler's trace
  export    JSON (round-trippable) and line-protocol dumps

Plain Python records directly (``get_registry().inc(...)``); served
programs return their counts as outputs. ``jit_inc``/``jit_gauge``/
``jit_observe`` are for ad-hoc debugging of traced code: no-ops unless
``enable_jit_metrics(True)`` was called before tracing.
"""
from repro.obs.export import (  # noqa: F401
    StreamingExporter, dump, from_dict, load, to_dict, to_json, to_lines,
)
from repro.obs.metrics import (  # noqa: F401
    BYTES_EDGES, COUNT_EDGES, FRACTION_EDGES, LATENCY_EDGES_S, MAX_SPANS,
    Counter, Gauge, Histogram, MetricsRegistry,
    enable_jit_metrics, get_registry, jit_gauge, jit_inc, jit_observe,
    reset_registry, set_registry, watch_compiles,
)
from repro.obs.trace import Span, current_span, span  # noqa: F401
