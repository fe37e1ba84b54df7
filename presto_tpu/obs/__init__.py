"""Observability plane: span trees + metrics registry.

One subsystem unifying the engine's stats silos (see
docs/observability.md): `Trace`/`TRACES` for per-query span trees that
survive retry and merge across the fleet, `METRICS` for the
process-wide Prometheus-rendered registry.
"""

from .metrics import METRICS, MetricsRegistry
from .span import (
    TRACES,
    Span,
    Trace,
    TraceStore,
    render_critical_path,
)

__all__ = [
    "METRICS",
    "MetricsRegistry",
    "TRACES",
    "Span",
    "Trace",
    "TraceStore",
    "render_critical_path",
]
