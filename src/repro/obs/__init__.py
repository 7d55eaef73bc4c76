"""Flight recorder: in-scan telemetry rings, metrics, timeline export.

See :mod:`repro.obs.ring` (device-side event rings +
:class:`FlightRecorder`), :mod:`repro.obs.metrics` (registry +
``sync_budget`` guard) and :mod:`repro.obs.timeline` (Chrome-trace /
Perfetto rendering).  ``python -m repro.obs`` runs the record→flush→
render smoke; ``python -m repro.obs render`` produces a trace JSON from
a fresh fleet run.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      SyncBudgetExceeded, counter_property,
                      global_registry, reset_global, sync_budget, to_host)
from .ring import (EV_EXCHANGE, EV_PASS, EV_SERVE, EVENT_NAMES,
                   EXCHANGE_FIELDS, FIELDS_BY_KIND, PASS_FIELDS,
                   PAYLOAD_WIDTH, SERVE_FIELDS, FlightRecorder,
                   RingEvents, TelemetryRing, flush, merge_events,
                   payload_column, record, ring_init)
from .timeline import (timeline_summary, to_chrome_trace,
                       validate_chrome_trace, write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "SyncBudgetExceeded", "counter_property", "global_registry",
    "reset_global", "sync_budget", "to_host",
    "EV_EXCHANGE", "EV_PASS", "EV_SERVE", "EVENT_NAMES",
    "EXCHANGE_FIELDS", "FIELDS_BY_KIND", "PASS_FIELDS", "PAYLOAD_WIDTH",
    "SERVE_FIELDS", "FlightRecorder", "RingEvents", "TelemetryRing",
    "flush", "merge_events", "payload_column", "record", "ring_init",
    "timeline_summary", "to_chrome_trace", "validate_chrome_trace",
    "write_chrome_trace",
]
