"""Profiler hooks — program spans on the device trace's clock.

``start_profiling(trace_dir)`` opens a device trace
(``jax.profiler.start_trace``).  While it is open, every ``obs.span`` also
enters a ``jax.profiler.TraceAnnotation`` of the same name (``annotation``),
so engine flushes, solver launches and the dispatcher's waits land in the
``.xplane.pb`` beside the device ops, on the profiler's clock.

With no trace open a span pays one module-flag check here, and nothing at
all under ``REPRO_OBS_DISABLED=1``.  jax is imported lazily inside the
functions so ``repro.obs`` itself stays importable (and stdlib-only) in
tools that never touch the accelerator stack.
"""
from __future__ import annotations

import threading
from typing import Optional

from repro.obs import metrics as _metrics

_lock = threading.Lock()
_trace_dir: Optional[str] = None
_annotation = None  # jax.profiler.TraceAnnotation while a trace is open


def profiling_active() -> bool:
    """True between ``start_profiling`` and ``stop_profiling``."""
    return _trace_dir is not None


def annotation(name: str):
    """A ``TraceAnnotation(name)``, not yet entered, while a trace is open;
    None otherwise.  No tags: the profiler would fold them into the event's
    name (``name#k=v#``), and the trace's readers match names exactly."""
    cls = _annotation
    return None if cls is None else cls(name)


def start_profiling(trace_dir: str) -> bool:
    """Start a jax profiler trace into ``trace_dir`` (TensorBoard /
    ``xprof``-loadable).  Returns False (and stays inert) when obs is
    disabled or jax's profiler is unavailable; raises on a genuinely bad
    start (e.g. a second concurrent trace) so misuse is not silent."""
    global _trace_dir, _annotation
    if not _metrics.enabled():
        return False
    try:
        from jax import profiler
    except ImportError:
        return False
    with _lock:
        if _trace_dir is not None:
            raise RuntimeError(
                f"profiling already active (writing {_trace_dir!r})")
        profiler.start_trace(trace_dir)
        _trace_dir = trace_dir
        _annotation = profiler.TraceAnnotation
    return True


def stop_profiling() -> Optional[str]:
    """Stop the active trace; returns its directory (None if idle)."""
    global _trace_dir, _annotation
    with _lock:
        if _trace_dir is None:
            return None
        from jax import profiler

        _annotation = None
        profiler.stop_trace()
        out, _trace_dir = _trace_dir, None
    return out
