"""Unified tracing + metrics layer.

Three pieces (docs/OBSERVABILITY.md is the user guide):

* :mod:`.trace` — nested span tracer with counters/gauges and a
  Chrome-trace / Perfetto JSON exporter; no-op when disabled.
* :mod:`.comm` — collective-communication accounting threaded through
  the in-jit collective face (``chainermn_tpu.ops.collective``) and the
  eager communicators (op, axis, payload bytes, dtype, host latency).
* :mod:`.metrics` — step-time breakdown / throughput / MFU published
  through the trainer observation path so the values are rank-aggregated
  like any other metric.

Fleet layer (ISSUE 2):

* :mod:`.aggregate` — per-rank trace shard merge (one Perfetto lane per
  rank) and the cross-rank skew report naming the straggler rank.
* :mod:`.anomaly` — rolling-window detectors (step-time spikes, loss
  NaN/divergence, comm-bytes drift, MFU drop) behind the
  :class:`HealthMonitor` trainer extension.
* :mod:`.export` — Prometheus textfile + versioned JSONL metrics stream
  (:class:`MetricsReport`) and the :func:`health_snapshot` dict the
  Watchdog dumps before aborting a stalled gang.

Production triad (ISSUE 5):

* :mod:`.flight` — black-box flight recorder: bounded ring of recent
  structured events every emitter tees into, dumped as an atomic
  versioned **debug bundle** on Watchdog abort / uncaught exception /
  SIGTERM / SIGUSR1 (``scripts/explain_bundle.py`` renders it).
* :mod:`.slo` — :class:`GoodputLedger` wall-time attribution
  (compute/comm/host/compile/queue-wait/stall), :class:`SLOTracker`
  multi-window burn-rate alerting, :class:`ReservoirSample` O(1)-memory
  percentiles.
* :mod:`.introspect` — live ``/statusz`` / ``/metricsz`` / ``/requestz``
  / ``/debugz`` HTTP endpoint (``--statusz-port`` in the train/serve
  CLIs).

Quick start::

    import chainermn_tpu as mn
    mn.observability.enable()
    ... train ...
    mn.observability.export_chrome_trace("trace.json")   # load in Perfetto
    print(mn.observability.comm_report())                # bytes per collective
"""

from .trace import (  # noqa: F401
    Tracer,
    add_counter,
    async_event,
    complete_event,
    disable,
    enable,
    enabled,
    export_chrome_trace,
    get_tracer,
    instant,
    now_us,
    reset,
    set_gauge,
    span,
    traced,
)
from .comm import (  # noqa: F401
    CommAccountant,
    accounted_method,
    collective,
    get_accountant,
)
from .metrics import (  # noqa: F401
    StepBreakdownReport,
    peak_flops_for,
)
from .aggregate import (  # noqa: F401
    cross_rank_report,
    find_shards,
    local_rank_summary,
    merge_trace_shards,
    shard_path,
)
from .anomaly import (  # noqa: F401
    CommBytesDriftDetector,
    HealthMonitor,
    LossAnomalyDetector,
    MFUDropDetector,
    StepTimeSpikeDetector,
    default_detectors,
)
from .export import (  # noqa: F401
    SCHEMA as METRICS_SCHEMA,
    MetricsReport,
    MetricsWriter,
    health_snapshot,
    parse_prometheus_text,
    prometheus_text,
    read_metrics_jsonl,
    write_prometheus_textfile,
)
from .flight import (  # noqa: F401
    BUNDLE_SCHEMA,
    FlightRecorder,
    dump_bundle,
    find_bundles,
    get_flight_recorder,
    install_signal_handlers,
    install_tracer_tee,
    read_bundle,
    register_provider,
    set_crash_dump_dir,
)
from .slo import (  # noqa: F401
    GoodputLedger,
    ReservoirSample,
    SLOTracker,
)
from .introspect import (  # noqa: F401
    StatusServer,
    start_status_server,
)


def comm_report():
    """Cumulative per-collective byte/call/latency totals."""
    return get_accountant().report()


def reset_all() -> None:
    """Clear trace events AND comm totals (tests, fresh capture)."""
    reset()
    get_accountant().reset()


__all__ = [
    "Tracer",
    "enable",
    "disable",
    "enabled",
    "reset",
    "reset_all",
    "span",
    "traced",
    "instant",
    "add_counter",
    "set_gauge",
    "get_tracer",
    "export_chrome_trace",
    "CommAccountant",
    "get_accountant",
    "collective",
    "accounted_method",
    "comm_report",
    "StepBreakdownReport",
    "peak_flops_for",
    # fleet layer (ISSUE 2)
    "shard_path",
    "find_shards",
    "merge_trace_shards",
    "local_rank_summary",
    "cross_rank_report",
    "HealthMonitor",
    "StepTimeSpikeDetector",
    "LossAnomalyDetector",
    "CommBytesDriftDetector",
    "MFUDropDetector",
    "default_detectors",
    "METRICS_SCHEMA",
    "MetricsWriter",
    "MetricsReport",
    "read_metrics_jsonl",
    "health_snapshot",
    "prometheus_text",
    "parse_prometheus_text",
    "write_prometheus_textfile",
    # flight recorder / SLO / introspection (ISSUE 5)
    "BUNDLE_SCHEMA",
    "FlightRecorder",
    "get_flight_recorder",
    "install_tracer_tee",
    "install_signal_handlers",
    "set_crash_dump_dir",
    "register_provider",
    "dump_bundle",
    "read_bundle",
    "find_bundles",
    "GoodputLedger",
    "ReservoirSample",
    "SLOTracker",
    "StatusServer",
    "start_status_server",
    "async_event",
    "complete_event",
    "now_us",
]
