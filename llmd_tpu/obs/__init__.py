"""Observability plane: metrics registry + tracing + dashboards (SURVEY §5,
reference docs/operations/observability/).

Metrics registry API (``llmd_tpu.obs.metrics``)
-----------------------------------------------

A dependency-free Prometheus-style registry shared by every layer::

    from llmd_tpu.obs import Registry

    reg = Registry()
    reqs = reg.counter("llm_d_epp_requests_total", "Requests received")
    depth = reg.gauge("llm_d_epp_flow_queue_depth", "Queued requests")
    lat = reg.histogram("llmd_tpu:engine_step_duration_seconds",
                        "Step wall time", labelnames=("phase",),
                        buckets=(0.001, 0.01, 0.1, 1.0))

    reqs.inc()
    depth.set(3)
    lat.labels(phase="unified").observe(0.012)
    text = reg.expose()          # Prometheus text format, fully escaped

Semantics:

* ``counter`` / ``gauge`` / ``histogram`` / ``summary`` register a family;
  re-registering the same name returns the existing family (type-checked),
  so components can share one registry without coordination.
* ``labels(**kv)`` returns the child for one label-value set; label values
  are escaped at exposition time (``escape_label_value``) — quotes,
  backslashes, and newlines in values can never corrupt the output.
* Histograms emit cumulative ``_bucket{le=...}`` series closed by
  ``+Inf``, plus ``_sum`` and ``_count``; summaries emit ``_sum``/``_count``.
* ``set_function(fn)`` attaches a scrape-time callback to an unlabeled
  counter/gauge — how legacy counter dicts surface without dual bookkeeping.
* Everything is thread-safe: the engine step-loop thread increments while
  aiohttp handlers expose.

``register_engine_metrics`` / ``register_engine_server_metrics`` /
``register_router_metrics`` declare the full family set each layer emits
(``llmd_tpu:*``, ``vllm:*``-compat, ``llm_d_epp_*``, ``igw_*``);
``tools/lint_metrics.py`` cross-checks the Grafana dashboards, alert rules,
and PromQL cookbook against these declarations in CI.

Flight recorder (``llmd_tpu.obs.events``)
-----------------------------------------

``FlightRecorder`` keeps an always-on, bounded ring of per-request event
timelines (arrival → routing → flow control → admission → prefill/decode →
retire) queryable via ``/debug/requests`` on both servers, with SLO tail
capture force-retaining (and force-tracing) slow requests. Histograms accept
``observe(v, exemplar={"trace_id": ...})`` and render OpenMetrics exemplar
annotations so dashboards can jump from a latency bucket to the trace.
See observability/flight-recorder.md.
"""

from llmd_tpu.obs.device import DeviceMonitor
from llmd_tpu.obs.events import (
    EVENT_CATALOG,
    FlightRecorder,
    RequestRecord,
)
from llmd_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    Summary,
    escape_label_value,
    register_device_metrics,
    register_engine_metrics,
    register_engine_server_metrics,
    register_router_metrics,
)
from llmd_tpu.obs.tracing import (
    Span,
    TracingConfig,
    Tracer,
    extract_traceparent,
    format_traceparent,
)

__all__ = [
    "Counter",
    "DeviceMonitor",
    "EVENT_CATALOG",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "Registry",
    "RequestRecord",
    "Span",
    "Summary",
    "Tracer",
    "TracingConfig",
    "escape_label_value",
    "extract_traceparent",
    "format_traceparent",
    "register_device_metrics",
    "register_engine_metrics",
    "register_engine_server_metrics",
    "register_router_metrics",
]
