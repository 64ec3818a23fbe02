"""Observability subsystem: metrics, histograms, request traces, the
engine flight recorder, the process watch, and Prometheus exposition.
fei_tpu/utils/metrics.py re-exports the METRICS singleton from here so
pre-existing call sites are unchanged."""

from fei_tpu.obs.flight import FLIGHT, CompileObserver, FlightRecorder
from fei_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    METRICS,
    Histogram,
    Metrics,
)
from fei_tpu.obs.proc import WATCH, ProcessWatch
from fei_tpu.obs.registry import METRIC_REGISTRY, declared, help_for
from fei_tpu.obs.render import snapshot_lines
from fei_tpu.obs.trace import TRACES, RequestTrace, TraceBuffer

__all__ = [
    "DEFAULT_BUCKETS",
    "FLIGHT",
    "METRICS",
    "METRIC_REGISTRY",
    "CompileObserver",
    "FlightRecorder",
    "Histogram",
    "Metrics",
    "ProcessWatch",
    "RequestTrace",
    "TRACES",
    "TraceBuffer",
    "WATCH",
    "declared",
    "help_for",
    "snapshot_lines",
]
