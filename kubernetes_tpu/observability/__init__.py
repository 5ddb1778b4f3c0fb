"""Observability layer: span tracing with a flight recorder, and the
cross-component metrics scrape surface (ISSUE 11).

Components emit spans through a SpanTracer (clock-injectable — the
chaos/serving determinism contract extends to traces) into a bounded
FlightRecorder; component metric registries aggregate into one
MetricsRegistry the APIServer serves at GET /metrics, next to
/debug/traces and /debug/pending."""

from .registry import MetricsRegistry, parse_exposition
from .tracer import (DEFAULT_POD_SAMPLE, FlightRecorder, NULL_TRACER,
                     Span, SpanTracer, nearest_rank_percentile,
                     thread_cpu_by_role)

__all__ = [
    "DEFAULT_POD_SAMPLE", "FlightRecorder", "MetricsRegistry",
    "NULL_TRACER", "Span", "SpanTracer", "nearest_rank_percentile",
    "parse_exposition", "thread_cpu_by_role",
]
