"""Unified observability layer: metrics, request tracing, exporters.

Everything in this package is host-side Python and nothing is traced:
the tracer's one jax import is the profiler bridge, and ``hlo_scopes``
reads a compiled program's text.  Engines bump counters / open spans
strictly outside jit, so instrumentation can never introduce a retrace;
the only sanctioned in-trace touch point is a *trace-time* counter bump
(the compile-spy pattern), which executes once per compilation and
costs zero per executed step.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    default_registry,
)
from repro.obs.hlo import hlo_scopes
from repro.obs.trace import NullTracer, Span, Tracer
from repro.obs.obs import Observability
from repro.obs.export import (
    json_snapshot,
    parse_prometheus_text,
    prometheus_text,
    write_json_snapshot,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "NullTracer",
    "Observability",
    "Span",
    "Tracer",
    "default_registry",
    "hlo_scopes",
    "json_snapshot",
    "parse_prometheus_text",
    "prometheus_text",
    "write_json_snapshot",
]
