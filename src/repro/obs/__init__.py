"""Observability: span tracing + Chrome-trace export (see tracer.py), and
scoped spans on the profiler's clock (see scope.py)."""

from .export import (chrome_trace_events, export_chrome_trace,
                     validate_chrome_trace)
from .scope import span
from .tracer import Span, Trace, Tracer

__all__ = ["Span", "Trace", "Tracer", "chrome_trace_events",
           "export_chrome_trace", "span", "validate_chrome_trace"]
