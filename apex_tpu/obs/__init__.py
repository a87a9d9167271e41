"""apex_tpu.obs — the zero-dependency runtime telemetry layer.

The PR 4 sanitizer suite proves the framework's invariants *statically*
(jaxpr/HLO); this package records what actually happens at *runtime* —
entirely host-side, so instrumentation can never add an op, a transfer,
or a recompile to a compiled program:

- :mod:`~apex_tpu.obs.metrics` — deterministic counters / gauges /
  exact-quantile histograms in a :class:`MetricsRegistry`
  (``ServeEngine.stats()`` is now a snapshot shim over one of these);
- :mod:`~apex_tpu.obs.trace` — the monotonic-clock nestable
  :class:`Tracer`: spans around every dispatch boundary in the train
  driver and every ServeEngine phase, each tagged
  executed-vs-compiled via the PR 4 ``CompileMonitor`` bridge, each
  with the main thread's and the process's CPU time, and (the ambient
  tracer) the garbage collector's pauses beside them;
- :mod:`~apex_tpu.obs.windows` — :func:`train_windows`: a train loop's
  wall time laid out window by window (the gap before it, its enqueue,
  the host's time while it is in flight, the blocked fetch), from those
  spans — live, or from an exported ``trace.jsonl``;
- :mod:`~apex_tpu.obs.lifecycle` — per-request TTFT / inter-token
  latency / queue-delay histograms from the engine's boundary
  timestamps;
- :mod:`~apex_tpu.obs.slo` — the LIVE half (ISSUE 10): sliding-window
  tail quantiles (:class:`WindowedHistogram`), declarative SLO
  objectives with multi-rate error-budget burn alerts
  (:class:`SloTracker`) and the machine-readable :class:`SloReport`
  the serve scheduler's SLO-aware admission consults at every
  boundary;
- :mod:`~apex_tpu.obs.flightrec` — the black box (ISSUE 11): an
  always-on bounded ring of structured boundary events (train
  dispatches, serve boundaries, fleet routing decisions, fault
  firings, SLO alert transitions, checkpoint saves) dumped as a
  machine-readable ``flightrec.jsonl`` postmortem on any resilience
  recovery or unrecoverable failure; ``APEX_TPU_FLIGHTREC=0`` kill
  switch, free under ``APEX_TPU_OBS=0``;
- :mod:`~apex_tpu.obs.gangview` — per-rank GANG telemetry (ISSUE 15):
  epoch-fenced K-boundary rows next to the exchange blobs, merged
  into a deterministic gang timeline with per-rank skew histograms
  and slowest-rank exchange-wait attribution (the train-side
  straggler detector); ``APEX_TPU_GANG_TELEMETRY=0`` kill switch;
- :mod:`~apex_tpu.obs.aggregate` — live fleet aggregation
  (ISSUE 15): the router scrapes per-host registries every N rounds
  into fleet-level :class:`WindowedHistogram`\\ s, one merged
  host/role-labeled OpenMetrics file, and live MFU/roofline gauges
  joining the cost census with measured dispatch walls;
- :mod:`~apex_tpu.obs.export` — JSONL event log + Chrome/Perfetto
  ``trace_event`` JSON (``tools/trace_report.py`` renders the text
  summary; :func:`apex_tpu.pyprof.parse.parse_chrome_trace` ingests
  the Chrome form) + the OpenMetrics text exposition
  (:func:`to_openmetrics`) so snapshots scrape like Prometheus.

Kill switch: ``APEX_TPU_OBS=0`` (spans/events become shared no-ops,
the ``jit.*`` counters stop and no ``gc`` callback is installed; the
engine's ``stats()`` counters keep working — they are accounting,
not telemetry).  ``APEX_TPU_OBS_TRACE_DIR=<dir>`` makes tier-1
(``tools/run_tier1.sh --trace <dir>``) export the ambient trace at
session end.
"""
from apex_tpu.obs.aggregate import (  # noqa: F401
    FleetAggregator,
    fleet_scrape_rounds,
)
from apex_tpu.obs.export import (  # noqa: F401
    SCHEMA,
    export_default,
    read_jsonl,
    to_openmetrics,
    write_chrome_trace,
    write_flightrec_line,
    write_jsonl,
    write_openmetrics,
    write_slo_line,
)
from apex_tpu.obs.flightrec import (  # noqa: F401
    FlightRecorder,
    NULL_FLIGHTREC,
    default_flightrec,
    flightrec_enabled,
    read_flightrec,
    reset_default_flightrec,
    set_flightrec_override,
)
from apex_tpu.obs.gangview import (  # noqa: F401
    GangTelemetry,
    deterministic_view,
    gang_telemetry_enabled,
    gang_view_digest,
    merge_gang_view,
    read_gang_rows,
)
from apex_tpu.obs.lifecycle import (  # noqa: F401
    NULL_LIFECYCLE,
    RequestLifecycle,
)
from apex_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from apex_tpu.obs.slo import (  # noqa: F401
    SloObjective,
    SloReport,
    SloTracker,
    WindowedHistogram,
    parse_objective,
    slo_admission_default,
)
from apex_tpu.obs.trace import (  # noqa: F401
    JIT_EVENTS,
    NULL_TRACER,
    Span,
    Tracer,
    default_registry,
    default_tracer,
    enabled,
    reset_default,
    set_enabled_override,
)
from apex_tpu.obs.windows import train_windows  # noqa: F401

__all__ = [
    "JIT_EVENTS",
    "SCHEMA",
    "Counter",
    "FleetAggregator",
    "FlightRecorder",
    "GangTelemetry",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_FLIGHTREC",
    "NULL_LIFECYCLE",
    "NULL_TRACER",
    "RequestLifecycle",
    "SloObjective",
    "SloReport",
    "SloTracker",
    "Span",
    "Tracer",
    "WindowedHistogram",
    "default_flightrec",
    "default_registry",
    "default_tracer",
    "deterministic_view",
    "enabled",
    "export_default",
    "fleet_scrape_rounds",
    "flightrec_enabled",
    "gang_telemetry_enabled",
    "gang_view_digest",
    "merge_gang_view",
    "parse_objective",
    "read_gang_rows",
    "read_flightrec",
    "read_jsonl",
    "reset_default",
    "reset_default_flightrec",
    "set_enabled_override",
    "set_flightrec_override",
    "slo_admission_default",
    "to_openmetrics",
    "train_windows",
    "write_chrome_trace",
    "write_flightrec_line",
    "write_jsonl",
    "write_openmetrics",
    "write_slo_line",
]

# Made with the package, not at the first span: the compile bridge and the
# GC hook account for the process (set-up's tracing, compiles and cache
# loads happen before any span opens) only if they are there from the start.
# With obs off this is NULL_TRACER and nothing is installed.
default_tracer()
