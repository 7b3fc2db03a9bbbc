"""Compilations, where they happen.

JAX reports every backend compile (a fresh one, or one loaded from the
persistent cache) to ``jax.monitoring``. One listener counts them in the
process-global metrics registry and, when a span is open on the compiling
thread, records an ``xla.compile`` child of it — so a prefill bucket that
compiles lazily shows as seconds under one ``loop.prefill.dispatch`` and not
as a slow request nobody can explain.
"""

from __future__ import annotations

import threading

from .metrics import get_registry
from .tracing import current_span, trace_now

__all__ = ["watch_compiles"]

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_watching = False


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    reg = get_registry()
    reg.counter("dl4j_tpu_xla_compiles_total",
                "Backend compiles (fresh, or loaded from the persistent "
                "compile cache)").inc()
    reg.counter("dl4j_tpu_xla_compile_seconds_total",
                "Seconds spent in backend compiles").inc(duration)
    span = current_span()
    if span is not None:
        end = trace_now()
        span.tracer.record_span("xla.compile", parent=span,
                                start_time=end - duration, end_time=end)


def watch_compiles() -> None:
    """Register the listener, once for the process however often called
    (the engines and solvers call it when they are built)."""
    global _watching
    with _lock:
        if _watching:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True
