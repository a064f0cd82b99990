"""Unified telemetry layer: metrics registry, periodic JSONL sampler,
the per-stage report, and the program's span primitive. The registry is
stdlib-only — safe to update from the control plane's hot paths."""
from repro.core.obs.registry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, get_registry,
                                     quantile, scoped, set_registry)
from repro.core.obs.report import build_telemetry, render_report
from repro.core.obs.sampler import MetricsSampler
from repro.core.obs.spans import bind_instance, span

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "MetricsSampler", "bind_instance", "build_telemetry",
           "get_registry", "quantile", "render_report", "scoped",
           "set_registry", "span"]
