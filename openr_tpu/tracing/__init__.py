"""Causal convergence tracing (openr_tpu.tracing).

A `Tracer` (one per node, injected `Clock` so SimClock tests get
deterministic timestamps) mints `TraceContext`s at event origins and
modules record spans against contexts they receive through queue items
and KvStore flooding metadata.  `export` renders completed spans as a
Chrome-trace/Perfetto-compatible file.  `pipeline` holds the dispatch
phase registry + `PipelineProbe` (per-phase histograms, per-chip busy
gauges); `flight_recorder` the bounded post-mortem ring that auto-dumps
on invariant breach / chip quarantine / watchdog crash.  See
docs/Observability.md for the span catalogue and naming conventions.
"""

from openr_tpu.tracing.export import chrome_trace_events, write_chrome_trace
from openr_tpu.tracing.flight_recorder import FlightRecorder
from openr_tpu.tracing.pipeline import PipelineProbe, disabled_probe
from openr_tpu.tracing.tracer import NOOP_SPAN, Span, Tracer, disabled_tracer

__all__ = [
    "NOOP_SPAN",
    "FlightRecorder",
    "PipelineProbe",
    "Span",
    "Tracer",
    "chrome_trace_events",
    "disabled_probe",
    "disabled_tracer",
    "write_chrome_trace",
]
