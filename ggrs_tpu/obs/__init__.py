"""``ggrs_tpu.obs`` — pool-scale observability (DESIGN.md §12).

Three dependency-free pieces:

- :mod:`registry` — counters, gauges, fixed-bucket histograms with label
  sets; near-zero cost on the hot path and a shared null mode for
  metrics-off runs.
- :mod:`recorder` — the per-slot flight recorder: a bounded ring of
  recent events (state changes, faults, rollback decisions, wire
  digests) dumped on quarantine/eviction for post-mortems.
- :mod:`exporters` — Prometheus text exposition, JSON snapshots, and a
  stdlib HTTP scrape endpoint (``/metrics``, ``/healthz``, ``/trace``).
- :mod:`trace` — the span tracer (DESIGN.md §14): tick → crossing → slot
  spans in a bounded ring with Chrome/Perfetto trace-event export;
  ``Tracer(enabled=False)`` compiles the layer out.
- :mod:`forensics` — desync post-mortems: first-divergent-frame bisection
  over shared checksum histories and the :class:`DesyncReport` artifact.
- :mod:`timeline` — match-lifecycle timelines (DESIGN.md §28): the
  stable cross-host event schema, the 16-byte trace context, and the
  bounded per-match stores the fleet ferries over the harvest plane.
- :mod:`slo` — frame-budget SLOs (DESIGN.md §28): per-tier compliance
  counters on the shard, multi-window burn rates + the 503-on-burn
  verdict on the supervisor.

The bank-side numbers behind these come from the native stat harvest:
``HostSessionPool.scrape()`` dumps every slot's protocol/sync counters
(ping, kbps, send-queue length, last-acked frame, rollback depth, frame
advantage both ways) in ONE ctypes crossing per scrape
(``ggrs_bank_stats`` in native/session_bank.cpp), preserving the
one-crossing-per-tick invariant of DESIGN.md §8.

Quickstart (see README "Observability")::

    from ggrs_tpu.obs import Registry, start_http_server
    from ggrs_tpu.parallel import HostSessionPool

    reg = Registry()
    pool = HostSessionPool(metrics=reg)
    ...
    server = start_http_server(reg, port=9464)
    while running:
        pool.advance_all()          # one crossing (the tick)
        pool.scrape()               # one crossing (every slot's stats)
"""

from .registry import (
    Counter,
    DEFAULT,
    Gauge,
    Histogram,
    MultiRegistry,
    Registry,
    default_registry,
)
from .recorder import ChecksumHistory, FlightRecorder
from .trace import (
    NULL_TRACER,
    Tracer,
    default_tracer,
    validate_chrome_trace,
)
from .forensics import (
    DesyncReport,
    build_desync_report,
    first_divergent_frame,
)
from .exporters import (
    MetricsHTTPServer,
    MetricsServer,
    json_snapshot,
    prometheus_text,
    start_http_server,
    validate_exposition,
)
from .fleet_obs import (
    FleetObs,
    RegistryCollector,
    fleet_metrics_digest,
    histogram_quantile,
)
from .timeline import (
    MatchTimeline,
    TIMELINE_EVENTS,
    TRACE_CTX,
    TRACE_CTX_BYTES,
    TimelineStore,
    first_occurrence_order,
    format_timeline,
    match_trace_id,
    merge_timelines,
    pack_trace_ctx,
    timeline_event,
    unpack_trace_ctx,
)
from .slo import (
    BurnRateEngine,
    ShardSloMeter,
    SloPolicy,
)

__all__ = [
    "BurnRateEngine",
    "ChecksumHistory",
    "Counter",
    "DEFAULT",
    "DesyncReport",
    "FleetObs",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MatchTimeline",
    "MetricsHTTPServer",
    "MetricsServer",
    "MultiRegistry",
    "NULL_TRACER",
    "Registry",
    "RegistryCollector",
    "ShardSloMeter",
    "SloPolicy",
    "TIMELINE_EVENTS",
    "TRACE_CTX",
    "TRACE_CTX_BYTES",
    "TimelineStore",
    "Tracer",
    "build_desync_report",
    "default_registry",
    "default_tracer",
    "first_divergent_frame",
    "first_occurrence_order",
    "fleet_metrics_digest",
    "format_timeline",
    "histogram_quantile",
    "json_snapshot",
    "match_trace_id",
    "merge_timelines",
    "pack_trace_ctx",
    "prometheus_text",
    "start_http_server",
    "timeline_event",
    "unpack_trace_ctx",
    "validate_chrome_trace",
    "validate_exposition",
]
