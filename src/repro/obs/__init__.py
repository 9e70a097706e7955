"""Serving-tier observability (DESIGN.md §17).

Three pieces, layered so the hot path stays cheap:

* :mod:`repro.obs.trace` — a bounded ring-buffer :class:`TraceRecorder`
  emitting request-lifecycle / engine / cluster / join spans, stamped
  from the same pluggable clock chaos uses, so traces are deterministic
  under ``REPRO_CHAOS`` + VirtualClock.  Default-off: the module-level
  :data:`NULL_TRACE` no-op recorder is falsy, so every instrumentation
  site guards with ``if self.trace:`` and costs one attribute load +
  branch when tracing is disabled.
* :mod:`repro.obs.metrics` — always-on counters / gauges / streaming
  histograms with fixed log-spaced buckets, mergeable across replicas
  (and replica incarnations) exactly like ``Ledger.__add__``.
* :mod:`repro.obs.export` — Perfetto/Chrome ``trace_event`` JSON and
  counter-track timelines.

A live recorder's synchronous spans also enter ``jax.profiler``
annotations of the same name, so a profiler capture shows them on the
device trace's clock (jax is imported on the first live span only).
"""

from repro.obs.trace import (NULL_TRACE, NullRecorder, TraceRecorder,
                             TRACE_ENV_VAR, recorder_from_env, trace_of)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               registry_of)
from repro.obs.export import (chrome_trace_events, chrome_trace_json,
                              queue_depth_timeline, write_chrome_trace)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACE",
    "NullRecorder",
    "TRACE_ENV_VAR",
    "TraceRecorder",
    "chrome_trace_events",
    "chrome_trace_json",
    "queue_depth_timeline",
    "recorder_from_env",
    "registry_of",
    "trace_of",
    "write_chrome_trace",
]
