"""Observability: metrics registry, trace spans, recompile sentinel.

A leaf package — ``core`` and ``serving`` import it, never the reverse
— so instrumentation can reach any layer without cycles.  See the
README's "Observability" section for the metric catalog and the
CONTRIBUTING.md naming convention (``<layer>.<noun>[_<unit>]``).
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, SpanLog,
                      get_registry)
from .recompile import (EXPECTED_SHAPE_CHANGE_KINDS, HotPathRecompileError,
                        RecompileSentinel, state_shapes)
from .tracing import NULL_SPAN, Span, Tracer, finished_spans

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "SpanLog",
           "get_registry",
           "EXPECTED_SHAPE_CHANGE_KINDS", "HotPathRecompileError",
           "RecompileSentinel", "state_shapes",
           "NULL_SPAN", "Span", "Tracer", "finished_spans"]
