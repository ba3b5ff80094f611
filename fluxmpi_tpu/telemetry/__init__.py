"""Telemetry: metrics, tracing, flight recorder, watchdog, run health,
and the device plane.

The observability layer the reference never had (SURVEY.md §5: its only
timing is ad-hoc wall-clock deltas in example scripts). Four planes:

**Metrics plane** (PR 1) — aggregates over time:

- :class:`MetricsRegistry` — labeled counter/gauge/histogram instruments
  with explicit :meth:`~MetricsRegistry.flush` to pluggable sinks
  (:class:`JSONLSink` / :class:`MemorySink` / :class:`ConsoleSink`);
- built-in instrumentation recording into the *default* registry:
  eager collectives (``comm.*``), the data loader (``data.*``), the
  train-step ``metrics=`` hook (``train.*``);
- :class:`TrainingMonitor` — periodic device-memory snapshots,
  cross-host step-time aggregation (straggler flag), and a per-host
  heartbeat.

**Trace plane** (PR 2) — the questions metrics can't answer ("which
collective is every host stuck in?", "where did the ranks
desynchronize?"):

- :mod:`~fluxmpi_tpu.telemetry.tracing` — near-zero-cost spans
  (:func:`span` / :func:`instant`) into a bounded ring, exported as
  Chrome-trace/Perfetto JSON (merge hosts with
  ``scripts/merge_traces.py``);
- :mod:`~fluxmpi_tpu.telemetry.flight_recorder` — ring of the last N
  collective launches with monotonic sequence numbers; cross-host dump
  diffing (:func:`diff_flight_dumps`) localizes a desync to the exact
  collective;
- :mod:`~fluxmpi_tpu.telemetry.watchdog` — opt-in stall detector that
  dumps all-thread stacks, the flight-recorder tail, open spans, and a
  final registry flush to one artifact per host (also on ``SIGUSR1``).

**Run-health plane** (PR 7) — is the wall-clock buying training
progress, and is the run still sane:

- :mod:`~fluxmpi_tpu.telemetry.goodput` — :class:`GoodputTracker`
  attributes wall time into goodput/badput buckets (productive step,
  compile, data stall, checkpoint I/O, resume, preemption drain) and
  computes **live MFU** from XLA's operation count of the step
  (:mod:`fluxmpi_tpu.utils.flops`); per-run breakdowns via
  ``scripts/goodput_report.py``;
- :mod:`~fluxmpi_tpu.telemetry.anomaly` — :class:`AnomalyDetector`
  with NaN/Inf, loss-spike (EWMA z-score), step-time-regression, and
  data-stall rules; warn/halt policies; triggers emit an ``anomaly.*``
  trace instant and a diagnostics bundle built from the watchdog's
  dump machinery.

**Device plane** (PR 9) — what XLA and the HBM are actually doing,
below every host-side number:

- :mod:`~fluxmpi_tpu.telemetry.compileplane` —
  :class:`CompileMonitor` subscribes to ``jax.monitoring`` compile
  events (``compile.*`` metrics), attributes retraces to tagged jit
  functions, and feeds the ``steady_state_retrace`` anomaly rule (a
  compile after warmup = the silent perf killer), cross-checked
  against the goodput compile bucket;
- :mod:`~fluxmpi_tpu.telemetry.memory` — normalized per-device HBM
  stats (``memory.*`` gauges + peak watermark, folded into the
  monitor's cross-host gather), a :func:`jax.live_arrays` census, and
  OOM forensics: ``train_loop`` writes a ``fluxmpi_oom.<proc>.json``
  bundle on ``RESOURCE_EXHAUSTED`` before re-raising;
- anomaly-triggered auto-profiling
  (:mod:`fluxmpi_tpu.utils.profiling`) — ``step_time_regression`` /
  ``steady_state_retrace`` triggers (and ``SIGUSR2``) capture one
  bounded XPlane window into ``FLUXMPI_TPU_PROFILE_DIR``, rate-limited
  once per run.

Recording is always on for metrics and the flight recorder (updates are
a few dict/deque ops); span recording and the watchdog are opt-in
(:func:`tracing.configure` / ``init(trace=..., watchdog=...)`` /
``FLUXMPI_TPU_TRACE`` / ``FLUXMPI_TPU_WATCHDOG``). Metric *emission* is
opt-in via :func:`configure`, ``fluxmpi_tpu.init(telemetry=...)``, or
``FLUXMPI_TPU_TELEMETRY``. See docs/observability.md.
"""

from __future__ import annotations

import os
from typing import Any

from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from .schema import (  # noqa: F401
    SCHEMA,
    TRACE_SCHEMA,
    validate_flight_dump,
    validate_metric,
    validate_record,
    validate_trace_export,
    validate_watchdog_dump,
)
from .sinks import (  # noqa: F401
    ConsoleSink,
    JSONLSink,
    MemorySink,
    NullSink,
    Sink,
)
from .monitor import TrainingMonitor  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import (  # noqa: F401
    Tracer,
    get_tracer,
    instant,
    set_tracer,
    span,
    trace_enabled,
)
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    diff_dumps as diff_flight_dumps,
    get_flight_recorder,
    set_flight_recorder,
)
from .watchdog import (  # noqa: F401
    Watchdog,
    arm_watchdog,
    disarm_watchdog,
    get_watchdog,
    notify_progress,
)
from . import goodput  # noqa: F401
from .goodput import (  # noqa: F401
    GoodputTracker,
    get_goodput_tracker,
    set_goodput_tracker,
)
from . import anomaly  # noqa: F401
from .anomaly import (  # noqa: F401
    AnomalyDetector,
    get_anomaly_detector,
    set_anomaly_detector,
)
from . import modelstats  # noqa: F401
from .modelstats import (  # noqa: F401
    ModelStats,
    get_model_stats,
    set_model_stats,
)
from . import compileplane  # noqa: F401
from .compileplane import (  # noqa: F401
    CompileMonitor,
    get_compile_monitor,
    set_compile_monitor,
)
from . import memory  # noqa: F401
from . import export  # noqa: F401
from .export import (  # noqa: F401
    Exporter,
    get_exporter,
    set_exporter,
)
from . import fleet  # noqa: F401
from .fleet import (  # noqa: F401
    FleetCollector,
    get_fleet_collector,
    set_fleet_collector,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "SCHEMA",
    "TRACE_SCHEMA",
    "validate_record",
    "validate_metric",
    "validate_trace_export",
    "validate_flight_dump",
    "validate_watchdog_dump",
    "Sink",
    "JSONLSink",
    "MemorySink",
    "ConsoleSink",
    "NullSink",
    "TrainingMonitor",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "span",
    "instant",
    "trace_enabled",
    "FlightRecorder",
    "get_flight_recorder",
    "set_flight_recorder",
    "diff_flight_dumps",
    "Watchdog",
    "arm_watchdog",
    "disarm_watchdog",
    "get_watchdog",
    "notify_progress",
    "GoodputTracker",
    "get_goodput_tracker",
    "set_goodput_tracker",
    "AnomalyDetector",
    "get_anomaly_detector",
    "set_anomaly_detector",
    "ModelStats",
    "get_model_stats",
    "set_model_stats",
    "CompileMonitor",
    "get_compile_monitor",
    "set_compile_monitor",
    "Exporter",
    "get_exporter",
    "set_exporter",
    "FleetCollector",
    "get_fleet_collector",
    "set_fleet_collector",
    "configure",
    "shutdown",
]

_ENV_VAR = "FLUXMPI_TPU_TELEMETRY"


def configure(spec: Any = None) -> MetricsRegistry:
    """Wire emission for the default registry from a one-value spec.

    ``spec`` may be:

    - ``None`` — read the ``FLUXMPI_TPU_TELEMETRY`` env var (same forms
      below; no-op when unset);
    - ``"console"`` / ``True`` — attach a rank-0 :class:`ConsoleSink`;
    - any other string — treat as a path, attach a :class:`JSONLSink`;
    - a :class:`Sink` instance — attach it;
    - a :class:`MetricsRegistry` — install it as the default registry.

    Returns the (possibly new) default registry. Called by
    ``fluxmpi_tpu.init(telemetry=...)``; safe to call directly.
    Idempotent for equivalent specs — ``init()`` is idempotent, so a
    repeated bring-up must not attach the same sink twice.
    """
    if spec is None:
        spec = os.environ.get(_ENV_VAR) or None
        if spec is None:
            return get_registry()
    if isinstance(spec, MetricsRegistry):
        set_registry(spec)
        return spec
    reg = get_registry()
    if spec is True or spec == "console":
        if any(isinstance(s, ConsoleSink) for s in reg.sinks):
            return reg
        sink: Sink = ConsoleSink()
    elif isinstance(spec, Sink):
        if spec in reg.sinks:
            return reg
        sink = spec
    elif isinstance(spec, str):
        if any(
            isinstance(s, JSONLSink) and s.path == spec for s in reg.sinks
        ):
            return reg
        sink = JSONLSink(spec)
    else:
        raise ValueError(
            f"telemetry spec must be a path, 'console', a Sink, or a "
            f"MetricsRegistry; got {spec!r}"
        )
    reg.add_sink(sink)
    return reg


def shutdown() -> None:
    """Tear down the observability planes in failure-safe order: reset
    the serving plane FIRST (inference engine stopped, pending requests
    failed, KV pools dropped — it produces into every surface below),
    then stop the live exporter (socket closed, serving thread joined —
    the port is immediately rebindable, and no scrape ever observes a
    half-reset process), disarm the watchdog, export the trace ring
    (when a path was configured) then reset the tracer and the flight
    recorder ring, reset the run-health plane (goodput window + anomaly
    detector), the model-internals plane, and the device plane (compile
    monitor, HBM watermark,
    auto-profiler — state left armed would leak into the next init
    cycle), then flush and detach every sink on the default registry
    (instruments survive — a re-configured registry keeps its cumulative
    counters)."""
    try:
        # Lazy import: the serving plane needs jax; this package must
        # stay importable without it (same rule as the auto-profiler).
        from ..serving import shutdown as _serving_shutdown

        _serving_shutdown()
    except Exception:
        pass
    try:
        # The request-observability plane rides the serving plane (PR
        # 16): close the per-request JSONL stream and drop the burn
        # windows/offender samples BEFORE the trace ring is exported —
        # observe.shutdown() emits nothing, it only uninstalls.
        from ..serving import observe as _serving_observe

        _serving_observe.shutdown()
    except Exception:
        pass
    try:
        # BEFORE the exporter: the collector's polling thread scrapes
        # exporters — stop the consumer before its sources vanish (and
        # drop the straggler streak, the fault-plane leak rule).
        fleet.shutdown()
    except Exception:
        pass
    try:
        # Alongside the fleet observer: a resize request left armed
        # across init cycles would drain the NEXT run at its first
        # flush boundary.
        from ..fleet import resize as _resize

        _resize.shutdown()
    except Exception:
        pass
    try:
        export.shutdown()
    except Exception:
        pass
    try:
        disarm_watchdog()
    except Exception:
        pass
    try:
        tracing.shutdown()
    except Exception:
        pass
    try:
        # AFTER the export above: reset drops the ring the export just
        # saved. The flight recorder keeps its cumulative counters
        # (comm deltas stay monotonic) but drops the entries — run 1's
        # launches must not appear in run 2's hang dumps.
        tracing.reset()
        get_flight_recorder().clear()
    except Exception:
        pass
    try:
        goodput.shutdown()
    except Exception:
        pass
    try:
        anomaly.shutdown()
    except Exception:
        pass
    try:
        modelstats.shutdown()
    except Exception:
        pass
    try:
        compileplane.shutdown()
    except Exception:
        pass
    try:
        memory.shutdown()
    except Exception:
        pass
    try:
        # Lazy import: profiling lives in utils (it needs jax); the
        # telemetry package itself must stay importable without it.
        from ..utils.profiling import shutdown_auto_profiler

        shutdown_auto_profiler()
    except Exception:
        pass
    get_registry().close()
