"""Structured tracing: the serving clock, spans, and solve telemetry.

**The clock.** ``now()`` is THE timestamp source for the serving stack —
``time.perf_counter``.  The engine already timed solves with it while the
dispatcher stamped tickets with ``time.monotonic``; both are monotonic, but
they are distinct clocks with no guaranteed common epoch, so queue-wait
(dispatcher) plus solve-time (engine) did not reliably compose into
end-to-end latency.  Everything now reads ``obs.now()`` so durations and
absolute deadlines live on one timeline.

**Spans.** ``Tracer.span("engine.flush", bucket=..., method=...)`` is a
context manager recording wall time, nesting (per-thread stack → parent
name + depth) and free-form tags into an in-memory ring buffer, with an
optional JSONL sink for offline analysis.  While a profiler trace is open
(``repro.obs.profiling.start_profiling``) the same call also enters a
``jax.profiler.TraceAnnotation`` of the span's name, so the span lands on
the device trace's timeline too: one call per site, both records.  Spans
are for *structure* (what called what, where the time went inside one
flush); the aggregate story lives in the metrics registry.

**SolveTelemetry.** One record per served request — who (tenant), where
(bucket, kernel path, placement), how (warm/cold, batch kind/size), and
outcome (sweeps, SSE, converged, queue wait, deadline margin, error type).
The engine attaches it to every ``ServedSolve``; the async dispatcher
back-fills the queue-side fields on completion.  It is intentionally a
plain dataclass with an ``as_dict()`` — a log pipeline can ship it as-is.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from repro.obs import metrics as _metrics
from repro.obs import profiling as _profiling

#: The single serving clock (seconds, monotonic, highest resolution
#: available).  Compare/subtract only against other ``now()`` readings.
now = time.perf_counter


@dataclass
class SpanRecord:
    """One completed (or still-open) span."""

    name: str
    t_start: float
    t_end: Optional[float] = None
    tags: Dict[str, Any] = field(default_factory=dict)
    parent: Optional[str] = None
    depth: int = 0
    thread: str = ""

    @property
    def duration_s(self) -> Optional[float]:
        if self.t_end is None:
            return None
        return self.t_end - self.t_start

    def as_dict(self) -> dict:
        d = asdict(self)
        d["duration_s"] = self.duration_s
        return d


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    return str(v)


class Tracer:
    """Ring-buffered span recorder with per-thread nesting.

    ``capacity`` bounds memory (old spans are dropped, newest kept);
    ``jsonl_path`` (or a later ``set_sink``) additionally appends one JSON
    object per completed span.  Thread-safe: the ring and sink share one
    lock; the nesting stack is thread-local, so spans on different threads
    never see each other as parents.
    """

    def __init__(self, capacity: int = 2048,
                 jsonl_path: Optional[str] = None):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._local = threading.local()
        self._sink = None
        if jsonl_path:
            self.set_sink(jsonl_path)

    # ------------------------------------------------------------- sink
    def set_sink(self, path: Optional[str]) -> None:
        """Point the JSONL sink at ``path`` (None closes it)."""
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None
            if path:
                self._sink = open(path, "a", encoding="utf-8")

    def close(self) -> None:
        self.set_sink(None)

    # ------------------------------------------------------------ record
    @contextmanager
    def span(self, name: str, *, defer: Optional[list] = None, **tags):
        """Record one span; yields the (mutable) ``SpanRecord`` so the body
        can attach result tags.  While a profiler trace is open the span is
        also a ``TraceAnnotation`` of ``name`` (the tags stay in the ring).
        No-op (yields None) when obs is disabled.

        ``defer``: a list the finished record is appended to instead of
        the ring and sink; the caller hands it to ``commit`` later, e.g.
        once it has released a lock the span closed under, so the sink's
        file write never runs under that lock."""
        if not _metrics.enabled():
            yield None
            return
        stack: List[SpanRecord] = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        ann = _profiling.annotation(name)
        if ann is not None:
            ann.__enter__()
        rec = SpanRecord(
            name=name, t_start=now(),
            tags={k: _jsonable(v) for k, v in tags.items()},
            parent=stack[-1].name if stack else None,
            depth=len(stack), thread=threading.current_thread().name)
        stack.append(rec)
        try:
            yield rec
        finally:
            rec.t_end = now()
            if ann is not None:
                ann.__exit__(None, None, None)
            stack.pop()
            if defer is not None:
                defer.append(rec)
            else:
                self.commit([rec])

    def commit(self, recs: List[SpanRecord]) -> None:
        """Write finished span records to the ring and the sink."""
        with self._lock:
            for rec in recs:
                self._ring.append(rec)
                if self._sink is not None:
                    json.dump(rec.as_dict(), self._sink)
                    self._sink.write("\n")
            if recs and self._sink is not None:
                self._sink.flush()

    # ------------------------------------------------------------- reads
    def spans(self, name: Optional[str] = None) -> List[SpanRecord]:
        """Completed spans, oldest first (optionally filtered by name)."""
        with self._lock:
            out = list(self._ring)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer (ring buffer + optional JSONL sink)."""
    return _tracer


def span(name: str, *, defer: Optional[list] = None, **tags):
    """``get_tracer().span(...)`` — the standard instrumentation call."""
    return _tracer.span(name, defer=defer, **tags)


# -------------------------------------------------------- kernel-path relay
_dispatch_local = threading.local()


def record_dispatch(path: str, method: str = "", reason: str = "") -> None:
    """Note which kernel path a solve actually ran (called from the *eager*
    dispatch shims in ``repro.kernels.ops`` / ``repro.core.methods`` — never
    from code that jit traces, where it would only fire at compile time).

    Increments ``solver_dispatch_total{path,method}`` (and
    ``solver_fallback_total`` when ``reason`` names a fallback cause) on the
    default registry, and parks the path in a thread-local slot the serving
    engine pops (``consume_dispatch``) to stamp the request's
    ``SolveTelemetry.kernel_path`` — the solver call stack has no other
    channel back to the engine.
    """
    if not _metrics.enabled():
        return
    reg = _metrics.default_registry()
    reg.counter("solver_dispatch_total",
                "solver calls by kernel path actually executed").inc(
        1, path=path, method=method or "unknown")
    if reason:
        reg.counter("solver_fallback_total",
                    "solves re-routed off their requested kernel path").inc(
            1, method=method or "unknown", reason=reason)
    _dispatch_local.last = path


def consume_dispatch(default: Optional[str] = None) -> Optional[str]:
    """Pop the kernel path recorded by the last solve on this thread."""
    path = getattr(_dispatch_local, "last", None)
    _dispatch_local.last = None
    return path if path is not None else default


# ------------------------------------------------------------ solve records
@dataclass
class SolveTelemetry:
    """Per-request solve record (see module docstring).

    ``kernel_path`` is the dispatch route that actually executed —
    ``fused`` (whole-solve Pallas megakernel), ``persweep`` (per-sweep
    Pallas launch loop), ``xla`` (jit'd XLA solver), ``sharded`` (mesh
    backend) or ``vmap`` (stacked batch) — including silent fallbacks
    (e.g. a ``bakp_fused`` request whose coalesced width outgrew VMEM and
    re-routed to XLA), which ``method`` alone cannot show.

    ``queue_wait_s`` (submit → batch fire), ``lane_wait_s`` (the fired
    batch queued on its execution lane → the lane starting it) and
    ``deadline_margin_s`` (deadline − completion; negative = missed) are
    dispatcher-side and stay None on the synchronous engine path.

    The engine's host work around the solve, per solve unit (a coalesced
    group or vmapped stack shares its unit's): ``build_s`` (the flush's
    grouping and design lookups plus the unit's padding of y and a0, up to
    the solver call: the ``engine.build`` spans), ``solve_s`` (the solver
    call to its result being ready on the device, retries included),
    ``fetch_s`` (ready result → finished ``ServedSolve``: the divergence
    check's host reads, the coefficient and residual copies, SSE and
    telemetry: the ``engine.fetch`` spans).  Unset (None) on error
    results.  All
    timestamps/durations are on the ``obs.now()`` clock, so a request's
    ``queue_wait_s + lane_wait_s + build_s + solve_s + fetch_s`` fits in
    its ticket's latency.

    ``retries`` counts the retry-ladder steps the request's solve took
    (``repro.resilience``): 0 = first attempt succeeded; the ``method``/
    ``kernel_path`` fields describe the rung that finally served it.
    """

    request_id: str = ""
    tenant_id: Optional[str] = None
    bucket: Tuple[int, int] = (0, 0)
    method: str = ""
    kernel_path: str = "unknown"
    placement: str = "single"
    lane: str = ""                    # execution-lane label ("single:xla",
    # "mesh:obs_sharded", "serial", ...; "inline" = solved on the caller's
    # thread, e.g. a flush nested inside a lane work)
    batch_kind: str = "single"
    group_size: int = 1
    batch_size: int = 1
    warm_start: bool = False
    cache_hit: bool = False
    n_sweeps: int = 0
    sse: float = 0.0
    converged: bool = False
    retries: int = 0
    solve_s: float = 0.0
    build_s: Optional[float] = None
    fetch_s: Optional[float] = None
    queue_wait_s: Optional[float] = None
    lane_wait_s: Optional[float] = None
    deadline_margin_s: Optional[float] = None
    error_type: Optional[str] = None

    def as_dict(self) -> dict:
        return {k: _jsonable(v) for k, v in asdict(self).items()}
