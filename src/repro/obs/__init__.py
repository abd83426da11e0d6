"""repro.obs — observability for the fabric serving stack.

Telemetry in three coordinated pieces, all contextvar-scoped and all
zero-cost when no observer is active:

  * :mod:`repro.obs.trace` — wall-clock spans + point events
    (``tracing`` / ``span`` / ``event``). Host-side only; enabling
    tracing provably does not change compiled programs. Under a tracer
    each span is also a ``jax.profiler`` annotation.
  * :mod:`repro.obs.scopes` — the device scope names
    (``jax.named_scope``) of the model, the CiM linear and the fabric.
  * :mod:`repro.obs.metrics` — counters / gauges / histograms
    (``collecting`` / ``inc`` / ``set_gauge`` / ``observe``) with
    Prometheus text exposition.
  * :mod:`repro.obs.sinks` — JSONL event log and Prometheus scrape-file
    writers (``JsonlSink`` / ``read_jsonl`` / ``write_prometheus``).

:mod:`repro.obs.fallback` pins the canonical fallback-reason taxonomy
(``ragged_batch``, ``insufficient_devices``, ...) that the fabric layers
emit through :func:`record_fallback`.

See ``docs/observability.md`` for the metric-name table, sink formats,
and the ``link_clock_calibration`` semantics.
"""

from repro.obs.fallback import (
    FALLBACK_REASONS,
    REASON_INELIGIBLE,
    REASON_INSUFFICIENT_DEVICES,
    REASON_NO_BUCKET,
    REASON_RAGGED_BATCH,
    REASON_REPLICATION_FALLBACK,
    REASON_REQUESTED_SEQUENTIAL,
    classify_fallback,
    record_fallback,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    active,
    collecting,
    get_value,
    inc,
    observe,
    set_gauge,
)
from repro.obs.sinks import JsonlSink, read_jsonl, write_prometheus
from repro.obs.trace import Tracer, enabled, event, span, tracing

__all__ = [
    # trace
    "Tracer",
    "tracing",
    "span",
    "event",
    "enabled",
    # metrics
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "collecting",
    "active",
    "inc",
    "set_gauge",
    "observe",
    "get_value",
    # sinks
    "JsonlSink",
    "read_jsonl",
    "write_prometheus",
    # fallback taxonomy
    "REASON_RAGGED_BATCH",
    "REASON_INSUFFICIENT_DEVICES",
    "REASON_REPLICATION_FALLBACK",
    "REASON_REQUESTED_SEQUENTIAL",
    "REASON_INELIGIBLE",
    "REASON_NO_BUCKET",
    "FALLBACK_REASONS",
    "classify_fallback",
    "record_fallback",
]
