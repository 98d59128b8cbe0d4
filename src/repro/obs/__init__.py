"""``repro.obs`` — runtime telemetry for the Solver facade and the
connectivity service (DESIGN.md §12).

Three cooperating layers, all opt-in and all bounded:

* **Span tracing** (``obs.trace``): host-side ``span(...)`` context
  managers around every facade/service operation, tagged with plan
  provenance (backend, bucket, forced/autotune/heuristic) and tenant
  id; a fixed-capacity ring buffer of finished spans, each stamped
  ``ts_ns`` on the profiler's clock with its ``id`` and ``parent``;
  JSON-lines and Chrome ``trace_event`` (Perfetto) exporters that line
  up with a device profile; an opt-in ``jax.profiler`` annotation
  bridge; ``count_device`` counters that keep device scalars and sync
  only when read. Disabled (the default) it costs one flag check per
  call site and syncs nothing.
* **On-device metrics** (``obs.metrics``): a ``Metrics`` pytree of
  int32 counters + fixed-bucket histograms threaded through the
  absorb/delete jits like ``WorkCounters`` — the instrumented
  steady-state tick stays transfer-free; host materialization only at
  ``metrics.flush()`` via the audited ``queries.to_host`` sink.
* **Latency SLOs** (``obs.slo``): per-tenant and global p50/p90/p99
  request-latency histograms on the shared ``HistogramSpec`` bucket
  math, emitted into ``BENCH_service.json``.

``python -m repro.obs summary <trace.jsonl>`` renders a trace;
``python -m repro.obs perfetto <trace.jsonl> <out.json>`` converts one
for the Perfetto UI.
"""
from repro.obs.metrics import (COUNTERS, HIST_KINDS, WORK_SPEC,
                               HistogramSpec, Metrics, flush,
                               record_mutation, record_rebuild)
from repro.obs.slo import (DEFAULT_LATENCY_SPEC, LatencyHistogram,
                           SLORecorder, merge_recorders)
from repro.obs.trace import (EventLog, Span, Tracer, count, count_device,
                             disable, enable, enabled, span, tracer)

__all__ = [
    # trace
    "span", "count", "count_device", "enable", "disable", "enabled",
    "tracer", "Tracer", "Span", "EventLog",
    # metrics
    "Metrics", "HistogramSpec", "WORK_SPEC", "COUNTERS", "HIST_KINDS",
    "record_mutation", "record_rebuild", "flush",
    # slo
    "SLORecorder", "LatencyHistogram", "DEFAULT_LATENCY_SPEC",
    "merge_recorders",
]
