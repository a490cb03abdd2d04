"""Request-lifecycle tracing + metrics for the pipelined serving path.

The reference engine's entire observability story is per-step-type
``totalTime[]`` sums and socket byte counters (SURVEY.md §5.1,
src/dllama.cpp:54-64); our ``EngineStats``/``/stats`` inherited that
aggregate shape. After the async pipeline (PR 3) and fused admissions
(PR 4) the serving path is exactly the kind of system aggregates lie
about — where a slow request spent its time, whether overlap actually
happened, which lane stalled. This package is the three missing layers:

- **spans.py / trace.py** — per-request lifecycle spans and per-dispatch
  step slices in a bounded host-side ring, exported as Chrome trace-event
  JSON (Perfetto / chrome://tracing loadable): lanes as tracks,
  fused/pipelined steps as slices, admissions/finishes/flushes as
  instants. Zero syncs or locks in the pipelined dispatch half — slices
  are stamped at consume time, one step behind (dlint ``pipeline-sync``
  stays green); monotonic clocks only (``clock`` stays green).
- **metrics.py** — counters/gauges/fixed-bucket log-scale histograms
  (TTFT, inter-token gap, queue wait, step duration) with Prometheus
  text exposition, served at ``GET /metrics`` and bridged from the same
  ``/stats`` snapshot so the two endpoints reconcile.
- **logs.py** — one structured JSON line per request (the summary also
  attached to completion responses) plus startup config lines.

Pure stdlib (no numpy/jax): importable anywhere dlint runs, and
registered under dlint's ``clock``, ``host-sync``, and ``guarded-by``
checks. Entry points: ``Telemetry`` (the hub the scheduler, HTTP server,
and benchmark share), ``GET /metrics`` / ``GET /trace`` (server/http.py),
``--trace-path`` (dumped on drain). docs/OBSERVABILITY.md is the guide.
"""

from .hub import Telemetry
from .logs import JsonLogger, default_logger, log_event
from .metrics import (
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    LabelledHistogram,
    MetricsRegistry,
    log_buckets,
)
from .spans import RequestTrace, SpanEvent, SpanTracer, StepRecord
from .trace import (
    chrome_trace,
    dump_chrome_trace,
    merge_chrome_traces,
    tracer_chrome_trace,
)
from .tracectx import (
    PHASE_KEYS,
    TRACE_HEADER,
    PhaseAccumulator,
    TraceContext,
    trace_id_of,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "JsonLogger",
    "LATENCY_BUCKETS_S",
    "LabelledHistogram",
    "MetricsRegistry",
    "PHASE_KEYS",
    "PhaseAccumulator",
    "RequestTrace",
    "SpanEvent",
    "SpanTracer",
    "StepRecord",
    "TRACE_HEADER",
    "Telemetry",
    "TraceContext",
    "chrome_trace",
    "default_logger",
    "dump_chrome_trace",
    "log_buckets",
    "log_event",
    "merge_chrome_traces",
    "trace_id_of",
    "tracer_chrome_trace",
]
