"""Unified telemetry plane: structured tracing on the virtual clock,
windowed metrics, SLO timelines, and flight-recorder postmortems; and
:func:`span`, the engine's host-work spans on the profiler's clock.

See :mod:`repro.obs.schema` for the event model and cause taxonomy,
:mod:`repro.obs.trace` for the determinism contract, :mod:`repro.obs.spans`
for the profiler spans.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, pcts,
                      percentile)
from .schema import (EVENT_KINDS, PLAN_CAUSES, SchemaError, validate_event,
                     validate_events)
from .spans import span
from .timeline import SLOTimeline
from .trace import DEFAULT_TRIGGERS, OFF, Tracer
from .export import to_jsonl, to_perfetto, write_jsonl, write_perfetto

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "pcts",
    "percentile", "EVENT_KINDS", "PLAN_CAUSES", "SchemaError",
    "validate_event", "validate_events", "span", "SLOTimeline",
    "DEFAULT_TRIGGERS", "OFF", "Tracer", "to_jsonl", "to_perfetto",
    "write_jsonl", "write_perfetto",
]
