"""Observability: process-wide metrics registry + Prometheus exposition.

The reference stack's only telemetry is printed wall-clock spans
(``run_grpc_inference.py:195-216``) and the repo's own
:mod:`tpu_dist_nn.utils.profiling` counters — neither is visible while
the system RUNS. This package is the dependency-free (stdlib-only)
metrics layer the serving/training hot paths publish into:

  - :mod:`tpu_dist_nn.obs.registry` — ``Counter`` / ``Gauge`` /
    ``Histogram`` families with label support behind one process-wide
    :data:`~tpu_dist_nn.obs.registry.REGISTRY`, plus the bridge that
    teaches existing :class:`~tpu_dist_nn.utils.profiling.LatencyStats`
    objects to feed a histogram.
  - :mod:`tpu_dist_nn.obs.exposition` — Prometheus text-format
    rendering and the stdlib ``/metrics`` + ``/healthz`` HTTP endpoint
    (``tdn ... --metrics-port``).
  - :mod:`tpu_dist_nn.obs.runtime` — a background sampler publishing
    queue depth, in-flight rows, coalesce ratio, and host/device
    memory gauges.
  - :mod:`tpu_dist_nn.obs.trace` — request-scoped distributed tracing
    (Dapper-style): a span recorder behind one process-wide
    :data:`~tpu_dist_nn.obs.trace.TRACER`, ``x-tdn-trace`` wire
    propagation across the gRPC hop, and Chrome trace-event export
    served from ``GET /trace`` (``tdn trace`` pulls and saves it).
  - :mod:`tpu_dist_nn.obs.profile` — performance attribution: completed
    spans folded into a per-stage SELF-time breakdown (p50/p99/share
    per stage, per method), served from ``GET /profile`` (``tdn
    profile`` pretty-prints it; ``tdn metrics --aggregate --profile``
    merges a fleet's).
  - :mod:`tpu_dist_nn.obs.log` — structured JSON logging: event-shaped,
    trace-correlated, rate-limited records for the serving/engine
    operational paths (``tdn --log-json`` renders the whole process's
    logs as JSON lines).
  - :mod:`tpu_dist_nn.obs.timeseries` — a bounded in-memory ring the
    runtime sampler snapshots selected families into (default 5s x 1h),
    served as ``GET /timeseries`` — history without an external
    Prometheus.
  - :mod:`tpu_dist_nn.obs.slo` — declared objectives (latency,
    availability) evaluated from the ring's windowed deltas into
    fast/slow error-budget burn rates, the ``tdn_slo_*`` gauges,
    ``GET /slo``, and the rate-limited ``slo.burn`` event.
  - :mod:`tpu_dist_nn.obs.collect` — fleet collection: cross-replica
    trace stitching (one Chrome trace, a lane per process) and
    ``/profile`` merging behind ``tdn trace --aggregate`` /
    ``tdn metrics --aggregate --profile`` / the router's
    ``/trace/fleet``.
  - :mod:`tpu_dist_nn.obs.top` — the ``tdn top`` live ANSI dashboard
    over a router fleet or single server (rps, percentiles, slots,
    breaker state, SLO budget, sparklines).
  - :mod:`tpu_dist_nn.obs.goodput` — the goodput & MFU accounting
    plane: analytic per-launch FLOP models (FCNN rows, LM
    prefill/decode at their static kernel shapes) fed at the
    launch/fetch boundaries, every launch split exactly into
    ``useful + pad`` FLOPs with a pad taxonomy (bucket rows,
    idle/frozen slots, masked attention tails), one peak table
    for every process, ``tdn_mfu_ratio`` /
    ``tdn_pad_ratio{path}`` / ``tdn_goodput_flops_total{kind}`` /
    ``tdn_prefix_flops_saved_total``, and ``GET /goodput``.
  - :mod:`tpu_dist_nn.obs.incident` — the flight recorder: detectors
    on the sampler tick (SLO fast burn, error/shed spikes, breaker
    opens, drain/failover) plus crash hooks, each trigger freezing a
    diagnostic bundle (trace ring + profile + timeseries window + log
    ring + /slo + /metrics + manifest) into a bounded on-disk incident
    store; on a router the capture fans out to every replica and
    stitches the fleet trace. ``GET /debug/bundle``, ``GET
    /incidents``, ``tdn incident``, ``tdn debug bundle``.

Every metric this framework publishes is prefixed ``tdn_``; the
catalog lives in ``docs/OBSERVABILITY.md``. All updates are plain
host-side dict/float operations — nothing here ever touches a device
buffer or forces a fetch, so instrumentation stays off the XLA hot
path by construction.
"""

from tpu_dist_nn.obs.registry import (  # noqa: F401
    REGISTRY,
    Registry,
    bridge_latency_stats,
    histogram_quantile,
)
from tpu_dist_nn.obs.exposition import (  # noqa: F401
    MetricsServer,
    parse_prometheus_text,
    parsed_histogram_quantile,
    render,
    split_series,
    start_http_server,
)
from tpu_dist_nn.obs.timeseries import TimeSeriesRing  # noqa: F401
from tpu_dist_nn.obs.slo import (  # noqa: F401
    SLOTracker,
    availability_objective,
    latency_objective,
)
from tpu_dist_nn.obs.runtime import RuntimeSampler  # noqa: F401
from tpu_dist_nn.obs.trace import (  # noqa: F401
    SpanContext,
    TRACE_HEADER,
    TRACER,
    Tracer,
)
from tpu_dist_nn.obs.profile import (  # noqa: F401
    format_profile_table,
    profile_snapshot,
)
from tpu_dist_nn.obs.log import (  # noqa: F401
    LOG_RING,
    JsonFormatter,
    LogRing,
    get_logger,
    setup_json_logging,
)
from tpu_dist_nn.obs.goodput import (  # noqa: F401
    GOODPUT,
    GoodputTracker,
    LMFlopModel,
    fcnn_flops_per_row,
)
from tpu_dist_nn.obs.incident import (  # noqa: F401
    FlightRecorder,
    IncidentStore,
    capture_bundle,
    default_detectors,
    incident_routes,
    install_crash_hook,
)

__all__ = [
    "REGISTRY",
    "Registry",
    "bridge_latency_stats",
    "histogram_quantile",
    "MetricsServer",
    "parse_prometheus_text",
    "parsed_histogram_quantile",
    "render",
    "split_series",
    "start_http_server",
    "RuntimeSampler",
    "TimeSeriesRing",
    "SLOTracker",
    "latency_objective",
    "availability_objective",
    "SpanContext",
    "TRACE_HEADER",
    "TRACER",
    "Tracer",
    "profile_snapshot",
    "format_profile_table",
    "get_logger",
    "setup_json_logging",
    "JsonFormatter",
    "LogRing",
    "LOG_RING",
    "GOODPUT",
    "GoodputTracker",
    "LMFlopModel",
    "fcnn_flops_per_row",
    "FlightRecorder",
    "IncidentStore",
    "capture_bundle",
    "default_detectors",
    "incident_routes",
    "install_crash_hook",
]
