"""Observability plane (the seventh plane): typed metrics registry,
retire->reclaim latency tracing, per-request lifecycle spans, and the
engine's host phase clock.

See ``docs/observability.md`` for the metric catalog, span schema and
exporter formats."""

from .export import (
    chrome_trace,
    prometheus_text,
    spans_jsonl,
    validate_chrome_trace,
)
from .metrics import (
    NULL_INSTRUMENT,
    STATS_KEY_ALIASES,
    STEP_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    apply_aliases,
    get_registry,
    set_registry,
)
from .reclaim_trace import ReclaimTracer
from .spans import PHASES, Span, SpanRecorder, StepPhases

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "ReclaimTracer",
    "Span", "SpanRecorder", "StepPhases", "PHASES",
    "STATS_KEY_ALIASES", "STEP_BUCKETS", "NULL_INSTRUMENT",
    "apply_aliases", "get_registry", "set_registry",
    "chrome_trace", "prometheus_text", "spans_jsonl",
    "validate_chrome_trace",
]
