"""repro.obs — telemetry for the solver-serving stack.

Four pieces, all stdlib-only at import time (jax is touched lazily and
only by the profiler hooks):

  metrics.py    Counter / Gauge / Histogram (fixed log-spaced buckets) in a
                thread-safe ``MetricsRegistry``; ``snapshot()`` → plain
                dict, ``render_prometheus()`` → text exposition format.
  trace.py      ``now()`` — THE serving clock (``time.perf_counter``;
                queue-wait and solve-time compose because every component
                reads the same clock); ``span()`` — the one span API: a
                ring buffer + optional JSONL sink, and, while a profiler
                trace is open, a ``TraceAnnotation`` of the same name on
                the device trace; ``SolveTelemetry`` per-request records;
                the kernel-path relay (``record_dispatch``/
                ``consume_dispatch``) that lets the engine report which
                dispatch route a solve *actually* took.
  profiling.py  Opt-in ``start_profiling()``/``stop_profiling()`` around
                ``jax.profiler``'s trace; while it is open ``span()``
                annotates it.
  export.py     ``write_metrics_json`` and the stdlib-``http.server``
                Prometheus scrape endpoint (``start_metrics_server``).

Kill switch: ``REPRO_OBS_DISABLED=1`` makes every hook a no-op (checked per
call; ``set_enabled`` flips it at runtime for A/B overhead runs).

The serving stack (``repro.serve``), the kernel dispatch shims
(``repro.kernels.ops``, ``repro.core.methods``) and the launch drivers all
record here; ``bench/`` reads the telemetry and the spans on the chip.
"""
from repro.obs.export import (MetricsServer, start_metrics_server,
                              write_metrics_json)
from repro.obs.metrics import (COUNT_BUCKETS, LATENCY_BUCKETS, Counter,
                               Gauge, Histogram, MetricsRegistry,
                               default_registry, enabled, log_buckets,
                               set_enabled)
from repro.obs.profiling import (profiling_active, start_profiling,
                                 stop_profiling)
from repro.obs.trace import (SolveTelemetry, SpanRecord, Tracer,
                             consume_dispatch, get_tracer, now,
                             record_dispatch, span)

__all__ = [
    "COUNT_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "MetricsRegistry",
    "MetricsServer",
    "SolveTelemetry",
    "SpanRecord",
    "Tracer",
    "consume_dispatch",
    "default_registry",
    "enabled",
    "get_tracer",
    "log_buckets",
    "now",
    "profiling_active",
    "record_dispatch",
    "set_enabled",
    "span",
    "start_metrics_server",
    "start_profiling",
    "stop_profiling",
    "write_metrics_json",
]
