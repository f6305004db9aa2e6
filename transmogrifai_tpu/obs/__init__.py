"""obs/ — unified observability: span tracing, trace export, goodput
accounting, and the process-wide metrics registry.

The reference's `OpSparkListener` gave every run one coherent per-phase
metrics story; this package is the port's version of that, grown to
cover what a TPU-first stack actually loses time to (ML Goodput line of
work, PAPERS.md):

- `trace`   — thread-safe hierarchical `Span` tracer with contextvar
              propagation; `RunProfile` phases, per-stage DAG fits,
              ingest workers, sweep blocks, retry backoffs, and serving
              batches all open spans on the global `TRACER`; `pull` /
              `upload` / `uploading` span every crossing between host
              and device with its bytes, `train_passes()` groups the
              ring's spans by training pass
- `export`  — Chrome-trace/Perfetto JSON exporter (+ validation) and a
              JSONL structured event log with run correlation ids
- `goodput` — `GoodputReport`: spans + events rolled into productive /
              recompile / retry-backoff / ingest-wait / OOM-redo
              buckets that sum to wall time
- `metrics` — Counter/Gauge/Histogram registry (promoted from
              `serving/metrics.py`, whose re-export shim now warns)
              with a process-global `REGISTRY` the serving `/metrics`
              surface exposes alongside each service's own, and
              per-bucket trace-id EXEMPLARS on histograms
- `flight`  — crash flight recorder: a bounded lock-free ring of recent
              span/event/metric records, dumped atomically (Chrome
              trace + JSONL tail) on breaker open / quarantine /
              watchdog restart / SIGTERM / `/debug/dump`
- `slo`     — declarative SLOs (availability/latency/staleness) with
              multi-window multi-burn-rate alerting over the live
              registries: `/slo`, `slo_*` gauges, `slo_alert` events,
              GoodputReport `slo` section
- `smoke`   — `make trace-smoke`: tiny train+score with `--trace-out`,
              validates the Perfetto JSON and the goodput rollup;
              `slo_smoke` (`make slo-smoke`) proves the request-tracing
              / tail-sampling / flight-dump / burn-rate-alert loop
              end to end

Request-scoped tracing lives in `trace` too: W3C ``traceparent``
parse/format, `TraceContext`, the `RequestTrace` span buffer serving
fills per request, and the `TailSampler` that keeps errors + the slow
tail while head-sampling the healthy majority.
"""

from transmogrifai_tpu.obs.export import (  # noqa: F401
    EventLog, chrome_trace, emit_event, install_event_log,
    merge_chrome_traces, uninstall_event_log, validate_chrome_trace,
    write_chrome_trace)
from transmogrifai_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, get_recorder)
from transmogrifai_tpu.obs.goodput import (  # noqa: F401
    GoodputReport, build_report)
from transmogrifai_tpu.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, REGISTRY, get_registry)
from transmogrifai_tpu.obs.slo import (  # noqa: F401
    SLO, SLOEngine, SLOParams)
from transmogrifai_tpu.obs.trace import (  # noqa: F401
    RequestTrace, Span, TRACER, TailSampler, TraceContext, Tracer,
    TracingParams, add_event, current_span, format_traceparent,
    get_tracer, new_run_id, parse_traceparent)

__all__ = [
    "Span", "Tracer", "TRACER", "add_event", "current_span", "get_tracer",
    "new_run_id",
    "RequestTrace", "TraceContext", "TracingParams", "TailSampler",
    "parse_traceparent", "format_traceparent",
    "EventLog", "chrome_trace", "merge_chrome_traces", "emit_event",
    "install_event_log", "uninstall_event_log", "validate_chrome_trace",
    "write_chrome_trace",
    "GoodputReport", "build_report",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "get_registry",
    "FlightRecorder", "get_recorder",
    "SLO", "SLOEngine", "SLOParams",
]
