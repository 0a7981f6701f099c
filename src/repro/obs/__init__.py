"""Unified observability layer for the SSO runtime.

- :mod:`repro.obs.trace` — span tracer with Chrome/Perfetto export
- :mod:`repro.obs.metrics` — counter/gauge/histogram registry
- :mod:`repro.obs.summary` — per-epoch one-line structured summaries
- :mod:`repro.obs.ledger` — append-only cross-run performance ledger
- :mod:`repro.obs.live` — live sampler, Prometheus exporter, HTTP endpoint
- :mod:`repro.obs.regress` — noise-aware perf-regression sentinel stats

Deliberately dependency-free (stdlib only) and imported by
``repro.core.counters``, so it must never import from ``repro.core`` /
``repro.runtime`` at module scope (``live`` reaches
``repro.core.threads.spawn`` lazily at thread-start time).
"""
from repro.obs.ledger import (
    LedgerSchemaError, RunLedger, config_fingerprint, make_record,
)
from repro.obs.live import (
    LiveSampler, TelemetryServer, parse_prometheus_text, to_prometheus_text,
)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.summary import EpochSummarizer
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Tracer

__all__ = [
    "Tracer", "NULL_TRACER", "NULL_SPAN",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "EpochSummarizer",
    "RunLedger", "LedgerSchemaError", "make_record", "config_fingerprint",
    "LiveSampler", "TelemetryServer",
    "to_prometheus_text", "parse_prometheus_text",
]
