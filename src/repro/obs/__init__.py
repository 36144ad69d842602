"""Observability layer: structured tracing, flight recorder, exporters.

See DESIGN.md §8. The one entry point the rest of the codebase touches
is :class:`Obs` — an engine builds one from ``EngineConfig.obs`` and the
serving/runtime layers share it, so a single event stream covers
ingress → handoff → engine stages → merge → subscription fan-out.
"""

from repro.obs.trace import (NULL_SPAN, NULL_TRACER, NullTracer, Obs,
                             StepScope, Tracer, hooks_registered, to_host,
                             uploaded)
from repro.obs.flight import FlightRecorder
from repro.obs.export import (prometheus_text, read_jsonl,
                              validate_events, validate_exposition,
                              validate_jsonl, write_chrome, write_jsonl,
                              write_prometheus)
from repro.obs.freshness import FreshnessLedger, QueryFreshness
from repro.obs.health import HealthEvent, HealthMonitor
from repro.obs.serve import OpsServer

__all__ = [
    "Obs", "Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN", "StepScope",
    "hooks_registered", "to_host", "uploaded",
    "FlightRecorder", "read_jsonl", "validate_events", "validate_jsonl",
    "validate_exposition", "prometheus_text", "write_chrome", "write_jsonl",
    "write_prometheus",
    "FreshnessLedger", "QueryFreshness", "HealthMonitor", "HealthEvent",
    "OpsServer",
]
