"""Simulation telemetry: round counters, span timeline, metrics sinks.

Three layers (DESIGN.md §observability):

  * :class:`RoundStats` — per-round physics counters accumulated inside
    both engines when ``SimConfig.collect_stats`` is set, returned on
    ``SimResult.stats``;
  * :class:`Tracer` / :func:`chrome_trace` — host-side span tree of the
    entry points' calls (preparation, compile, execution) and of
    chunk/batch dispatches, on the profiler's clock, exportable as
    Chrome ``trace_event`` JSON; :func:`default_tracer` is the
    process-wide, always-on one;
  * :class:`MetricsSink` backends (:class:`InMemorySink`,
    :class:`JsonlSink`) — structured event consumers, wired to the CLI's
    ``--metrics-out``.

:func:`fit_device_models` closes the feedback loop: a recorded (or
re-loaded) trace becomes per-device ``loadbalance.DeviceModel`` fits.
"""

from repro.telemetry.sinks import InMemorySink, JsonlSink, MetricsSink
from repro.telemetry.stats import RoundStats
from repro.telemetry.trace import (
    SpanEvent,
    Tracer,
    chrome_trace,
    default_tracer,
    device_label,
    device_samples,
    fit_device_models,
    load_chrome_trace,
)

__all__ = [
    "InMemorySink",
    "JsonlSink",
    "MetricsSink",
    "RoundStats",
    "SpanEvent",
    "Tracer",
    "chrome_trace",
    "default_tracer",
    "device_label",
    "device_samples",
    "fit_device_models",
    "load_chrome_trace",
]
