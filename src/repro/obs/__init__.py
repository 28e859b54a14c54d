"""Unified observability: engine telemetry, serving spans, trace export.

Three layers, one package (PR 10):

  ``telemetry.py``     the per-round ENGINE telemetry channel — device-
                       computed per-superstep series (halt scalar +
                       per-program probes such as frontier counts)
                       appended to the superstep drivers' loop carry,
                       plus trace-time wire-byte accounting at the
                       exchange taps in ``core/partitioned.py``.
                       Telemetry on/off is a compile-cache dimension
                       (like ``guard=``); the off path is bit-identical
                       to a pre-telemetry build.
  ``spans.py``         the SERVING-path span/event model: a bounded
                       ring buffer of monotonic-timestamped spans
                       (admission → validate → coalesce-wait →
                       dispatch → device → demux → reply, plus
                       mutation / WAL-append / snapshot / recovery and
                       the checkpoint-runner's detection/rollback
                       events).
  ``registry.py``      the declared span kinds + instrument registry
                       (counters / gauges / histograms) with the
                       markdown-table generators ``docs/API.md`` is
                       drift-tested against.
  ``report.py``        derived views: the plain-text roll-up report,
                       ``trace_summary`` (what ``graph_serve --json``
                       publishes), and the latency cells derived from
                       query spans (reconciled against
                       ``serve/metrics.py`` in tests).
  ``scopes.py``        device scopes: the ``jax.named_scope`` names
                       the engine wraps its device work in, and
                       ``op_scopes``, which reads them back from a
                       compiled executable's HLO text so a profiler
                       trace's operations can be named by scope.
  ``trace_export.py``  Chrome trace-event (Perfetto-loadable) JSON:
                       per-component tracks for the server, plus the
                       schema validator the CI ``obs`` lane runs.

On the profiler's clock, ``spans.annotate`` marks the engine's host
phases as ``repro.<name>`` (``registry.PROGRAM_SPANS``), and an enabled
``SpanRecorder`` marks its serve spans as ``repro.<component>.<kind>``.

Layering: this package imports NOTHING from ``repro.core`` or
``repro.serve`` (numpy + stdlib, and ``jax`` lazily for the profiler
annotations and named scopes), so ``core/`` may call into it
(the drivers publish trace-time phase marks and the exchange taps
report payload bytes) without a cycle — mirroring ``core/faults.py``.
"""

from repro.obs.registry import COMPONENTS, DEVICE_SCOPES, INSTRUMENTS, \
    PROGRAM_SPANS, SPAN_KINDS, Registry, instruments_markdown_table, \
    scopes_markdown_table, spans_markdown_table
from repro.obs.report import derive_latency_cells, rollup, trace_summary
from repro.obs.scopes import compiled_scopes, device_scope, op_scopes
from repro.obs.spans import NULL_RECORDER, Event, Span, SpanRecorder, \
    annotate
from repro.obs.telemetry import PhaseSeries, RunTelemetry, WireRecord
from repro.obs.trace_export import chrome_trace, validate_chrome_trace, \
    write_trace

__all__ = [
    "COMPONENTS", "DEVICE_SCOPES", "Event", "INSTRUMENTS", "NULL_RECORDER",
    "PROGRAM_SPANS", "PhaseSeries", "Registry", "RunTelemetry",
    "SPAN_KINDS", "Span", "SpanRecorder", "WireRecord", "annotate",
    "chrome_trace", "compiled_scopes", "derive_latency_cells",
    "device_scope", "instruments_markdown_table", "op_scopes", "rollup",
    "scopes_markdown_table", "spans_markdown_table", "trace_summary",
    "validate_chrome_trace", "write_trace",
]
