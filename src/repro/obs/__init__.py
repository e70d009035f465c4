"""MLego observability layer: tracing and metrics.

Two pieces, one instrumentation story (see api/README.md
"Observability" for the user-facing tour):

* ``repro.obs.trace`` — `Span`/`Tracer` with a thread-safe ring
  buffer and Chrome-trace-event export (loads in Perfetto).  Span
  owners (session, service) hold a `Tracer`; everything else emits
  through the ambient thread-local context, so un-traced code paths
  cost one dict lookup.  Gap training splits into ``train.densify`` /
  ``train.fit`` / ``train.fetch``, a device merge into
  ``merge.stack`` / ``merge.kernel`` (inside ``kernel.launch``) and
  ``merge.finish``; transfers carry ``bytes`` (``device.upload``),
  ``bytes_in`` and ``bytes_out``; backend compiles land as
  ``jax.compile`` spans.  Under ``profile=True`` the owner's tracer
  mirrors each span into a ``jax.profiler.TraceAnnotation``, so the
  spans sit on the profiler's host plane, on its clock; nothing is
  compiled for it.
* ``repro.obs.metrics`` — `MetricsRegistry` of labelled counters/
  gauges/histograms with Prometheus text exposition and a JSON
  snapshot; the single read surface for every counter the serve
  stack used to scatter across ad-hoc structures.

Both are stdlib-only at import — importable from ``repro.core``
without cycles; the tracer imports jax only when an enabled tracer
opens its first span, to listen for compiles.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    HistogramView,
    MetricsRegistry,
)
from repro.obs.trace import (
    Span,
    Tracer,
    current_span,
    current_tracer,
    instant,
    set_attrs,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramView",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "current_span",
    "current_tracer",
    "instant",
    "set_attrs",
    "span",
]
