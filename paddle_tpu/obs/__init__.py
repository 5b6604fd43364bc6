"""paddle_tpu.obs — unified telemetry: metrics registry + tracing spans.

One process-wide, thread-safe sink for every system metric and span in
the framework (PR 6; docs/observability.md is the catalog):

- `registry` — counters / gauges / fixed-bucket histograms with exact
  p50/p90/p99, organized as labeled families in the process-wide
  `REGISTRY`. The serving engine's `EngineStats`, the training loop,
  the checkpoint manager and the elastic supervisor all record here.
- `trace` — nestable wall-clock spans with categories
  (prefill/decode/schedule/checkpoint/restart/...); absorbs the
  profiler's RecordEvent machinery (old API is a shim over this).
- `export` — JSON snapshot, Prometheus text format and chrome trace,
  on demand or periodically from a daemon thread.
- `reqtrace` — per-request causal event log (PR 13): a bounded ring of
  host-side lifecycle events keyed by stable trace ids that survive
  preemption, requeue and cross-engine failover, plus the armed flight
  recorder that dumps postmortem JSON artifacts on quarantine /
  failover / integrity failures. `tools/reqtrace.py` is the offline
  timeline / TTFT-decomposition / causality-check CLI over its dumps.

Importing this package pulls in stdlib + numpy only (no jax), so
tools/ptlint.py-style offline tooling can read metrics definitions
anywhere. Recording is host arithmetic on already-fetched values —
the telemetry layer adds ZERO device syncs (PT-T007 clean).
"""
from __future__ import annotations

from . import export, registry, reqtrace, trace
from .export import (SnapshotExporter, dump_snapshot, export_chrome_trace,
                     snapshot, to_prometheus)
from .registry import (DEFAULT_BUCKETS, Counter, Family, Gauge, Histogram,
                       MetricRegistry, REGISTRY)
from .reqtrace import ReqTraceRing, TraceEvent
from .trace import CATEGORIES, Span, SpanEvent, span

__all__ = [
    # registry
    "REGISTRY", "MetricRegistry", "Family", "Counter", "Gauge", "Histogram",
    "DEFAULT_BUCKETS", "counter", "gauge", "histogram",
    # trace
    "Span", "SpanEvent", "span", "CATEGORIES", "trace",
    # reqtrace
    "reqtrace", "ReqTraceRing", "TraceEvent",
    # export
    "snapshot", "dump_snapshot", "to_prometheus", "export_chrome_trace",
    "SnapshotExporter", "export", "registry",
]


def counter(name: str, help: str = "", labels=(), unit: str = "") -> Family:
    """Get-or-create a counter family in the default REGISTRY."""
    return REGISTRY.counter(name, help=help, labels=labels, unit=unit)


def gauge(name: str, help: str = "", labels=(), unit: str = "") -> Family:
    """Get-or-create a gauge family in the default REGISTRY."""
    return REGISTRY.gauge(name, help=help, labels=labels, unit=unit)


def histogram(name: str, help: str = "", labels=(), unit: str = "",
              buckets=DEFAULT_BUCKETS, sample_cap: int = 8192) -> Family:
    """Get-or-create a histogram family in the default REGISTRY."""
    return REGISTRY.histogram(name, help=help, labels=labels, unit=unit,
                              buckets=buckets, sample_cap=sample_cap)
