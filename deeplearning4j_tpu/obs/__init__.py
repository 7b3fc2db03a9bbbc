"""Process-wide observability: metrics registry, Prometheus exposition,
distributed tracing, step-time attribution, and the training-listener
bridge.

One registry (default process-global, injectable everywhere) is the single
source of truth for serving (``ParallelInference``, ``JsonModelServer``),
resilience (circuit/admission/retry/elastic_fit), training
(:class:`MetricsListener`), and data (``AsyncDataSetIterator``) signals;
``GET /metrics`` on ``JsonModelServer`` and ``UIServer`` exposes it in
Prometheus text format 0.0.4. See README "Observability" for the metric
naming convention and the ``stats()`` ↔ metrics mapping.

Tracing (``obs/tracing.py``): :class:`Tracer`/:class:`TraceSpan` give
requests identity (W3C ``traceparent``) and parent/child structure across
the client→server→engine hop, exported to a bounded :class:`TraceStore`
served by ``GET /v1/traces``; engine loop turns and training steps are
traces of the same tracer, mirrored into a ``jax.profiler`` session while
one collects, and ``obs/compiles.py`` counts backend compiles where they
happen. :class:`StepProfiler`
(``obs/step_profiler.py``) attributes training step time to
data_wait/h2d/compute/host phases with sampled device fencing. README
"Tracing & step-time attribution".
"""

from .listener import MetricsListener, MoEMetricsListener, record_moe_metrics
from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricError,
    MetricsRegistry,
    Span,
    get_registry,
    set_registry,
    trace,
)
from .prom import CONTENT_TYPE as PROM_CONTENT_TYPE
from .prom import render_prometheus
from .step_profiler import PHASES as STEP_PHASES
from .step_profiler import StepProfiler
from .tracing import (
    DEFAULT_SAMPLE_RATE as DEFAULT_TRACE_SAMPLE_RATE,
    TraceContext,
    TraceSpan,
    TraceStore,
    Tracer,
    current_context,
    current_span,
    decode_traceparent,
    encode_traceparent,
    get_tracer,
    set_tracer,
    trace_now,
)

__all__ = [
    "Counter",
    "DEFAULT_BUCKETS",
    "DEFAULT_TRACE_SAMPLE_RATE",
    "Gauge",
    "Histogram",
    "MetricError",
    "MetricsListener",
    "MetricsRegistry",
    "MoEMetricsListener",
    "PROM_CONTENT_TYPE",
    "STEP_PHASES",
    "Span",
    "StepProfiler",
    "TraceContext",
    "TraceSpan",
    "TraceStore",
    "Tracer",
    "current_context",
    "current_span",
    "decode_traceparent",
    "encode_traceparent",
    "get_registry",
    "get_tracer",
    "record_moe_metrics",
    "render_prometheus",
    "set_registry",
    "set_tracer",
    "trace",
    "trace_now",
]
