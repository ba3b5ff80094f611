"""Runtime bring-up, world identity, and the global device mesh.

TPU-native redesign of the reference's L3 runtime (reference: src/common.jl).
The reference world is MPI: ``mpiexecjl`` spawns one OS process per rank, each
rank binds one GPU round-robin (src/common.jl:16-45), and every collective
runs over ``MPI.COMM_WORLD``. The TPU world is SPMD over a named device mesh:
``init()`` optionally joins a multi-host pod slice
(``jax.distributed.initialize``), then builds a :class:`jax.sharding.Mesh`
over all global devices. XLA owns device binding — there is no analogue of
``CUDA.device!`` because every collective is compiled against the mesh.

Identity mapping (the reference collapses process == rank == GPU; a TPU
controller process drives several chips, so the two notions split):

- :func:`total_workers` — the number of data-parallel workers, i.e. global
  device count (reference: ``MPI.Comm_size``, src/common.jl:64-69; one worker
  held one GPU there, one worker is one TPU chip here).
- :func:`local_rank` — the rank of this controller process
  (reference: ``MPI.Comm_rank``, src/common.jl:52-57). Use
  :func:`process_count` / :func:`local_device_count` for the full picture.

Both queries raise ``FluxMPINotInitializedError`` before ``init()``
(reference: src/common.jl:53,65) and are safe inside differentiated code: they
return Python ints, invisible to tracing — the analogue of the reference's
``@non_differentiable`` marks (src/common.jl:57,69).
"""

from __future__ import annotations

import os
import signal
import warnings
from typing import Any, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from . import config
from .errors import FluxMPINotInitializedError, TopologyMismatchError

__all__ = [
    "init",
    "is_initialized",
    "Initialized",
    "enable_compile_cache",
    "shutdown",
    "local_rank",
    "total_workers",
    "process_index",
    "process_count",
    "device_count",
    "local_device_count",
    "global_mesh",
    "global_plan",
    "auto_parallel",
    "dp_axis_name",
    "preemption_requested",
    "request_preemption",
    "clear_preemption",
    "install_preemption_handlers",
    "uninstall_preemption_handlers",
    "preemption_handlers_installed",
]


class _RuntimeState:
    initialized: bool = False
    mesh: Mesh | None = None
    plan: Any = None  # the ResolvedPlan behind init(parallel=), if any
    distributed: bool = False
    # init(parallel="auto") / FLUXMPI_TPU_PARALLEL=auto was requested:
    # the mesh starts as the dp default and the layout autotuner's
    # winner is installed over it via _install_autotuned_plan.
    auto_parallel: bool = False


_state = _RuntimeState()


# ---------------------------------------------------------------------------
# Preemption plane: SIGTERM/SIGINT → a flag the training loop polls.
#
# TPU preemption delivers SIGTERM with a grace window; the handler must be
# signal-safe, so — same rule as the watchdog's SIGUSR1 handler — it ONLY
# sets a plain flag (no locks, no I/O, no jax). `train_loop` polls
# `preemption_requested()` at dispatch boundaries, drains its in-flight
# window, writes an emergency checkpoint, and returns cleanly with
# ``summary["preempted"] = True`` (see docs/fault_tolerance.md).
# ---------------------------------------------------------------------------

_PREEMPTION_ENV = "FLUXMPI_TPU_PREEMPTION"

_SIGNALS_BY_NAME = {
    "term": (signal.SIGTERM,),
    "int": (signal.SIGINT,),
    "both": (signal.SIGTERM, signal.SIGINT),
}


class _PreemptionState:
    requested: bool = False
    signum: int | None = None


_preemption = _PreemptionState()
_prev_signal_handlers: dict[int, Any] = {}


def preemption_requested() -> bool:
    """Has a preemption signal (or :func:`request_preemption`) arrived?
    One attribute read — cheap enough to poll every dispatch."""
    return _preemption.requested


def request_preemption(signum: int | None = None) -> None:
    """Set the preemption flag programmatically (what the signal handler
    does; also the test hook — no real signal needed)."""
    _preemption.requested = True
    _preemption.signum = signum


def clear_preemption() -> None:
    """Reset the flag (a driver that handled one preemption and decided
    to continue, or test teardown)."""
    _preemption.requested = False
    _preemption.signum = None


def _on_preemption_signal(signum: int, frame: Any) -> None:
    # Runs between bytecodes on the main thread: only a flag write is
    # safe here (the watchdog signal-safety rule — a handler that took a
    # registry/IO lock could deadlock the loop it is trying to stop).
    _preemption.requested = True
    _preemption.signum = signum


def install_preemption_handlers(
    signals: Sequence[int] = (signal.SIGTERM, signal.SIGINT),
) -> None:
    """Install the flag-setting handler for ``signals`` (idempotent; the
    previous handlers are remembered for
    :func:`uninstall_preemption_handlers`). Must run on the main thread;
    elsewhere the install is skipped with a warning (the flag can still
    be set via :func:`request_preemption`)."""
    for sig in signals:
        if sig in _prev_signal_handlers:
            continue
        try:
            _prev_signal_handlers[sig] = signal.signal(
                sig, _on_preemption_signal
            )
        except (ValueError, OSError) as exc:  # non-main thread / platform
            warnings.warn(
                f"cannot install preemption handler for signal {sig}: "
                f"{exc}; preemption polling still works via "
                f"request_preemption()",
                stacklevel=2,
            )


def preemption_handlers_installed() -> bool:
    """Is the flag-setting signal handler currently installed? The
    install is SPMD-consistent (same ``init(preemption=)`` / env on
    every process), so multi-process ``train_loop`` gates its
    coordinated preemption poll on this and every process answers
    alike."""
    return bool(_prev_signal_handlers)


def uninstall_preemption_handlers() -> None:
    """Restore the pre-install signal handlers and clear the flag."""
    for sig, prev in list(_prev_signal_handlers.items()):
        try:
            signal.signal(sig, prev)
        except (ValueError, OSError):
            pass
        del _prev_signal_handlers[sig]
    clear_preemption()


def _configure_preemption(spec: Any = None) -> None:
    """Wire preemption handling from a one-value spec (mirror of
    ``telemetry.configure``): ``None`` reads ``FLUXMPI_TPU_PREEMPTION``
    (no-op when unset); ``True``/``"1"``/``"both"`` installs
    SIGTERM+SIGINT; ``"term"``/``"int"`` installs just that signal;
    ``False``/``"0"`` uninstalls."""
    if spec is None:
        spec = os.environ.get(_PREEMPTION_ENV)
        if spec is None or spec == "":
            return
    if spec is False or spec == "0":
        uninstall_preemption_handlers()
        return
    if spec is True or spec == "1":
        spec = "both"
    if not isinstance(spec, str) or spec not in _SIGNALS_BY_NAME:
        raise ValueError(
            f"preemption spec must be a bool or one of "
            f"{sorted(_SIGNALS_BY_NAME)}; got {spec!r}"
        )
    install_preemption_handlers(_SIGNALS_BY_NAME[spec])


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache: fleet-scale cold start pays compile
# once (shared storage), not once per host — the AOT-lowered fused-window
# programs and every other jit land in it.
# ---------------------------------------------------------------------------

_COMPILE_CACHE_ENV = "FLUXMPI_TPU_COMPILE_CACHE"
# JAX's own variable: when it is set the cache lives there and this
# module sets no directory at all (the path is part of the cache key, so
# whoever placed the cache must be the only one to name it).
_JAX_COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_COMPILE_CACHE_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache(cache_dir: str | None = None) -> bool:
    """Turn on XLA's persistent compilation cache so repeat runs — and,
    on shared storage, every host of a fleet — skip the slow first
    compile. Returns True when enabled.

    Where the cache lives: ``JAX_COMPILATION_CACHE_DIR``, when set, wins
    outright — jax already reads it, and this function then sets no
    directory (``cache_dir`` and ``FLUXMPI_TPU_COMPILE_CACHE`` are
    ignored). Otherwise ``cache_dir``, else ``FLUXMPI_TPU_COMPILE_CACHE``,
    else ``<checkout>/.jax_cache`` next to the package (a fixed path:
    the directory is part of the cache key, so one that moves never
    hits).

    TPU only: XLA:CPU persists AOT executables keyed too loosely — an
    entry compiled on a host with different CPU features loads anyway
    ("may SIGILL") and in practice kills device threads, wedging
    multi-device collective rendezvous. On other backends this is a
    no-op (with a warning when the cache was explicitly requested)."""
    explicit = cache_dir is not None or bool(
        os.environ.get(_COMPILE_CACHE_ENV)
    )
    if jax.default_backend() != "tpu":
        if explicit:
            warnings.warn(
                "persistent compile cache skipped: XLA:CPU persists AOT "
                "executables keyed too loosely across hosts (stale "
                "entries can SIGILL device threads); the cache is "
                "TPU-only",
                stacklevel=2,
            )
        return False
    if not os.environ.get(_JAX_COMPILE_CACHE_ENV):
        jax.config.update(
            "jax_compilation_cache_dir",
            cache_dir
            or os.environ.get(_COMPILE_CACHE_ENV)
            or _COMPILE_CACHE_DEFAULT_DIR,
        )
    return True


def _configure_compile_cache(spec: Any = None) -> None:
    """Wire the persistent compile cache from a one-value spec (mirror
    of ``telemetry.configure``): ``None`` reads
    ``FLUXMPI_TPU_COMPILE_CACHE`` (no-op when unset); a path string
    enables the cache there; ``True``/``"1"`` enables the default
    location; ``False``/``"0"`` is a no-op (the cache config is
    process-global jax state — there is nothing to detach)."""
    if spec is None:
        spec = os.environ.get(_COMPILE_CACHE_ENV)
        if spec is None or spec == "":
            return
    if spec is False or spec == "0":
        return
    if spec is True or spec == "1":
        enable_compile_cache()
        return
    if isinstance(spec, str):
        enable_compile_cache(spec)
        return
    raise ValueError(
        f"compile_cache spec must be a bool, '0'/'1', or a directory "
        f"path; got {spec!r}"
    )


def _same_rule_config(a: Any, b: Any) -> bool:
    """Do two ParallelConfigs declare the same partition-rule behavior?
    Tables compare by value, callables by identity (== on functions)."""
    try:
        same_rules = a.rules is b.rules or a.rules == b.rules
    except Exception:
        same_rules = False
    return (
        bool(same_rules)
        and a.strict == b.strict
        and a.fsdp_min_size == b.fsdp_min_size
    )


def _same_plan(parallel: Any, installed: Any) -> bool:
    """Is the ``parallel=`` argument of an idempotent ``init`` replay the
    layout already installed? True for the installed plan itself, its
    source config, an equivalent re-resolved plan, or a config declaring
    the same axis sizes/names AND rule behavior (rules/strict/
    fsdp_min_size — a replay changing the rule table must warn, not
    silently keep the old one) — replaying the same declaration must
    stay warning-free."""
    if installed is None:
        return False
    if parallel is installed or parallel is installed.config:
        return True
    sizes = getattr(parallel, "sizes", None)
    names = getattr(parallel, "axis_names", None)
    if not (isinstance(sizes, dict) and isinstance(names, dict)):
        return False
    cfg = installed.config
    other = getattr(parallel, "config", None)
    if other is not None:
        # A re-resolved ResolvedPlan: its sizes/axis_names are the
        # RESOLVED mesh-axes-only dicts — compare against the installed
        # plan's resolved layout, not the raw config (whose six-axis,
        # possibly -1 declaration can never dict-equal it).
        return (
            sizes == installed.sizes
            and names == installed.axis_names
            and _same_rule_config(other, cfg)
        )
    if not _same_rule_config(parallel, cfg):
        return False
    if sizes == cfg.sizes and names == cfg.axis_names:
        return True
    # Different declaration, possibly the same layout (dp=-1 vs dp=8):
    # resolve against the installed mesh's devices and compare the
    # resolved layouts.
    try:
        resolved = parallel.resolve(list(installed.mesh.devices.flat))
    except Exception:
        return False
    return (
        resolved.sizes == installed.sizes
        and resolved.axis_names == installed.axis_names
    )


def _should_init_distributed() -> bool:
    """Heuristic for joining a multi-host world at ``init()``.

    The reference always calls ``MPI.Init()`` because ``mpiexecjl`` created
    the world (src/common.jl:22). On TPU the world exists iff we run on a pod
    slice or the coordinator is configured explicitly.
    """
    if os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    ):
        return True
    # Cloud TPU pod slice: multiple workers announced by the TPU VM runtime.
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h]) > 1


def init(
    *,
    devices: Sequence[jax.Device] | None = None,
    mesh_shape: dict[str, int] | None = None,
    parallel: Any = None,
    distributed: bool | None = None,
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    verbose: bool = False,
    telemetry: Any = None,
    trace: Any = None,
    watchdog: Any = None,
    preemption: Any = None,
    faults: Any = None,
    goodput: Any = None,
    anomaly: Any = None,
    model_stats: Any = None,
    compileplane: Any = None,
    memory: Any = None,
    profile: Any = None,
    compile_cache: Any = None,
    export: Any = None,
    serving: Any = None,
    request_log: Any = None,
    fleet: Any = None,
    resize: Any = None,
) -> Mesh:
    """Bring up the fluxmpi_tpu runtime. Idempotent.

    TPU-native analogue of ``FluxMPI.Init`` (reference: src/common.jl:16-45):

    - joins the multi-host world when on a pod slice (analogue of
      ``MPI.Init()`` joining the mpiexec world, src/common.jl:22);
    - builds the global device mesh (analogue of rank→GPU round-robin
      binding, src/common.jl:31-42 — on TPU the mesh *is* the binding);
    - warns when running with a single worker (parity with
      src/common.jl:25-27).

    Args:
      devices: devices to build the mesh over; defaults to all global devices.
      mesh_shape: ordered ``{axis_name: size}``; one size may be ``-1``
        (inferred). Defaults to a 1-D data-parallel mesh
        ``{config.DP_AXIS_NAME: ndevices}``. Soft-deprecated in favor of
        ``parallel=`` (which also derives partition rules, batch specs,
        and the axis names every parallelism module shares); kept for
        ad-hoc meshes. Mutually exclusive with ``parallel``.
      parallel: a :class:`~fluxmpi_tpu.parallel.ParallelConfig` (or an
        already-resolved plan) — the declarative N-D layout. The global
        mesh is the plan's mesh, the resolved plan is installed as
        :func:`global_plan` (consumed by ``make_train_step(parallel=)``,
        pipeline/ring/ulysses axis-name defaults, checkpoint manifests,
        and the ``/status`` PARALLEL board). Raises
        :class:`~fluxmpi_tpu.errors.TopologyMismatchError` when the
        plan's axes cannot cover the devices. The string ``"auto"``
        (also reachable via ``FLUXMPI_TPU_PARALLEL=auto`` when neither
        ``parallel=`` nor ``mesh_shape=`` is passed) arms the layout
        autotuner instead: the mesh comes up as the dp default and
        :func:`fluxmpi_tpu.parallel.autotune.autotune` — which needs
        the model — installs its banked or freshly-trialed winner as
        the global plan (see docs/performance.md, "Auto layout").
      distributed: force (or forbid) ``jax.distributed.initialize``; default
        auto-detects a pod slice / explicit coordinator.
      coordinator_address, num_processes, process_id: forwarded to
        ``jax.distributed.initialize`` when joining explicitly.
      verbose: print world info from every rank (reference ``verbose`` kwarg,
        src/common.jl:16).
      telemetry: wire metric emission at bring-up — a JSONL path,
        ``"console"``, a :class:`~fluxmpi_tpu.telemetry.Sink`, or a
        :class:`~fluxmpi_tpu.telemetry.MetricsRegistry` to install as the
        default (see :func:`fluxmpi_tpu.telemetry.configure`). ``None``
        defers to the ``FLUXMPI_TPU_TELEMETRY`` env var (no-op when
        unset). Applied even on already-initialized (idempotent) calls so
        a notebook can attach a sink late.
      trace: wire span tracing at bring-up — ``True`` enables recording
        into the bounded ring, a path additionally exports Chrome-trace
        JSON there at :func:`shutdown` (``{process}`` in the path is
        formatted per host); see
        :func:`fluxmpi_tpu.telemetry.tracing.configure`. ``None`` defers
        to ``FLUXMPI_TPU_TRACE``.
      watchdog: arm the hang watchdog — ``True`` or a deadline in
        seconds (stall → per-host dump of thread stacks, the collective
        flight-recorder tail, open spans, and a final registry flush;
        ``SIGUSR1`` dumps on demand); see
        :func:`fluxmpi_tpu.telemetry.watchdog.configure`. ``None``
        defers to ``FLUXMPI_TPU_WATCHDOG``. Like ``telemetry``, both are
        applied on idempotent replays too.
      preemption: install the preemption-signal handler — ``True`` (or
        ``"both"``) catches SIGTERM+SIGINT, ``"term"``/``"int"`` just
        one; the handler only sets a flag that
        :func:`~fluxmpi_tpu.parallel.train_loop` polls at dispatch
        boundaries (drain, emergency checkpoint, clean return). ``None``
        defers to ``FLUXMPI_TPU_PREEMPTION``; see
        docs/fault_tolerance.md.
      faults: arm a fault-injection schedule (grammar in
        :mod:`fluxmpi_tpu.faults`, e.g. ``"comm.allreduce@step=7"``).
        ``None`` defers to ``FLUXMPI_TPU_FAULTS``; ``False`` disarms.
      goodput: enable the run-health goodput plane — ``True`` turns on
        wall-clock badput attribution + live MFU in
        :func:`~fluxmpi_tpu.parallel.train_loop` (see
        :mod:`fluxmpi_tpu.telemetry.goodput`), or pass a
        :class:`~fluxmpi_tpu.telemetry.GoodputTracker` to install
        custom wiring. ``None`` defers to ``FLUXMPI_TPU_GOODPUT``.
      anomaly: install the anomaly detector — ``True`` = defaults (NaN
        loss/grad halt the loop cleanly, statistical rules warn),
        ``"warn"`` = observe-only, or an
        :class:`~fluxmpi_tpu.telemetry.AnomalyDetector`; on trigger an
        ``anomaly.*`` instant + a diagnostics bundle are emitted (see
        :mod:`fluxmpi_tpu.telemetry.anomaly`). ``None`` defers to
        ``FLUXMPI_TPU_ANOMALY``. All the observability/robustness specs
        are applied on idempotent replays too.
      model_stats: install the model-internals plane — ``True`` makes
        ``make_train_step`` fold a per-layer stats tree into the
        compiled program (per-layer gradient/parameter norms,
        update-to-weight ratios, nonfinite counts for NaN provenance,
        gradient noise scale on shard_map steps) that ``train_loop``
        emits as ``model.*`` metrics at flush boundaries; an int sets
        the leaf-path grouping depth, or pass a
        :class:`~fluxmpi_tpu.telemetry.ModelStats`. ``None`` defers to
        ``FLUXMPI_TPU_MODEL_STATS`` (depth/top-k knobs:
        ``FLUXMPI_TPU_MODEL_STATS_DEPTH`` /
        ``FLUXMPI_TPU_MODEL_STATS_TOPK``). See
        :mod:`fluxmpi_tpu.telemetry.modelstats`.
      compileplane: install the compile/retrace monitor — ``True``
        subscribes to ``jax.monitoring`` compile events, emits
        ``compile.*`` metrics at ``train_loop`` flush boundaries, and
        arms the ``steady_state_retrace`` anomaly rule (see
        :mod:`fluxmpi_tpu.telemetry.compileplane`); or pass a
        :class:`~fluxmpi_tpu.telemetry.CompileMonitor`. ``None`` defers
        to ``FLUXMPI_TPU_COMPILEPLANE``.
      memory: enable the HBM plane — ``True`` turns on per-device
        ``memory.*`` gauges + the peak watermark and folds the local
        peak into :class:`~fluxmpi_tpu.telemetry.TrainingMonitor`'s
        cross-host gather (see :mod:`fluxmpi_tpu.telemetry.memory`;
        OOM forensics bundles are written regardless — they ride the
        error path). ``None`` defers to ``FLUXMPI_TPU_MEMORY``.
      profile: arm anomaly-triggered auto-profiling — a logdir path
        captures one bounded XPlane window there on
        ``step_time_regression`` / ``steady_state_retrace`` triggers
        (and on ``SIGUSR2``), rate-limited to once per run; see
        :func:`fluxmpi_tpu.utils.profiling.configure_auto_profiler`.
        ``None`` defers to ``FLUXMPI_TPU_PROFILE_DIR`` (window/limit
        from ``FLUXMPI_TPU_PROFILE_SECONDS`` /
        ``FLUXMPI_TPU_PROFILE_LIMIT``).
      compile_cache: point XLA's persistent compilation cache at a
        directory (``True`` = the default location) so repeat runs —
        and, on shared storage, every host of a fleet — skip the slow
        first compile; the fused-window AOT programs land in it too
        (see :func:`enable_compile_cache`; TPU only — a warning names
        why elsewhere). ``None`` defers to
        ``FLUXMPI_TPU_COMPILE_CACHE``.
      export: start the live export plane — an in-process HTTP server
        (stdlib, daemon thread) serving Prometheus ``/metrics``, a
        ``/status`` JSON snapshot, and a ``/healthz`` liveness probe
        keyed to the watchdog's progress clock (503 when progress
        stalls past the deadline — orchestrator-restartable). ``True``
        serves on the default port (9307), a port number on that port,
        or pass an :class:`~fluxmpi_tpu.telemetry.Exporter`; ``None``
        defers to ``FLUXMPI_TPU_EXPORT_PORT`` (bind address from
        ``FLUXMPI_TPU_EXPORT_ADDR``). Poll a fleet with
        ``scripts/fluxmpi_top.py``; see docs/observability.md
        "Live export".
      serving: set the serving plane's fleet defaults — ``True`` (or a
        dict with ``slots`` / ``block_size`` / ``num_blocks`` /
        ``max_queue``) seeds
        :class:`~fluxmpi_tpu.serving.InferenceEngine` geometry,
        otherwise read from ``FLUXMPI_TPU_SERVING`` (+ ``_SLOTS`` /
        ``_BLOCK_SIZE`` / ``_BLOCKS`` / ``_QUEUE``); ``False`` resets
        the plane (any running engine stopped). See docs/serving.md.
      request_log: install the serving request-observability plane —
        ``True`` arms it in-memory (per-request lifecycle spans on the
        trace ring, KV-pool forensics, SLO burn accounting), a path
        additionally appends one schema'd JSONL record per terminal
        request there (``{process}`` formatted per host; aggregate with
        ``scripts/serving_report.py``), or pass a
        :class:`~fluxmpi_tpu.serving.RequestObserver` for custom SLO
        thresholds. ``None`` defers to ``FLUXMPI_TPU_REQUEST_LOG``
        (long burn window from ``FLUXMPI_TPU_SLO_WINDOW``); ``False``
        resets. See docs/observability.md "Serving plane".
      fleet: install the fleet plane — ``True`` arms the per-host skew
        ingredients (the monitor's gather grows the collective-block /
        flight-sequence columns, train_loop posts the FLEET board) and,
        on process 0, starts the cross-host
        :class:`~fluxmpi_tpu.telemetry.FleetCollector` scraping every
        armed host's ``/status``; a path string additionally appends
        one ``fluxmpi_tpu.fleet/v1`` snapshot per collect there (read
        back with ``scripts/fleet_report.py``), or pass a
        :class:`~fluxmpi_tpu.telemetry.FleetCollector` for custom
        hosts / interval / thresholds. ``None`` defers to
        ``FLUXMPI_TPU_FLEET`` (+ ``_FLEET_HOSTS`` / ``_FLEET_INTERVAL``);
        ``False`` resets (collector stopped). See docs/observability.md
        "Fleet plane".
      resize: arm the live-resize plane
        (:mod:`fluxmpi_tpu.fleet.resize`) — ``True``/``"1"`` arms it, a
        path string also banks one ``fluxmpi_tpu.resize/v1`` record per
        completed resize there, or pass a
        :class:`~fluxmpi_tpu.fleet.resize.ResizeCoordinator`. ``None``
        defers to ``FLUXMPI_TPU_RESIZE``; ``False`` disarms. With the
        plane armed and ``train_loop(checkpoint=...)`` attached,
        ``fluxmpi_tpu.fleet.resize.request_resize(M)`` drains the world
        at a flush boundary and hands off to an M-process relaunch.
        See docs/fault_tolerance.md "Zero-downtime ops".

    Returns:
      The global :class:`jax.sharding.Mesh`.
    """
    from .logging import fluxmpi_println  # local import: avoid cycle
    from .telemetry import anomaly as _anomaly
    from .telemetry import compileplane as _compileplane
    from .telemetry import configure as _configure_telemetry
    from .telemetry import export as _export
    from .telemetry import fleet as _fleet
    from .telemetry import goodput as _goodput
    from .telemetry import memory as _memory
    from .telemetry import modelstats as _modelstats
    from .telemetry import tracing as _tracing
    from .telemetry import watchdog as _watchdog
    from .utils import profiling as _profiling
    from . import faults as _faults_mod
    from . import serving as _serving
    from .fleet import resize as _resize
    from .serving import observe as _serving_observe

    # parallel="auto" (or FLUXMPI_TPU_PARALLEL=auto with no explicit
    # layout): arm auto mode. The mesh comes up as the 1-D dp default;
    # fluxmpi_tpu.parallel.autotune.autotune(...) later installs its
    # winner over it (same-process, pre-training) — init itself cannot
    # run trials because it does not know the model yet.
    auto_requested = False
    if isinstance(parallel, str):
        if parallel != "auto":
            raise ValueError(
                f'parallel= accepts a ParallelConfig, a ResolvedPlan, or '
                f'the string "auto", got {parallel!r}'
            )
        auto_requested = True
        parallel = None
    elif parallel is None and mesh_shape is None:
        env_parallel = os.environ.get("FLUXMPI_TPU_PARALLEL", "").strip()
        if env_parallel == "auto":
            auto_requested = True
        elif env_parallel:
            warnings.warn(
                f'ignoring FLUXMPI_TPU_PARALLEL={env_parallel!r} — the '
                f'only supported value is "auto" (pass a ParallelConfig '
                f'to init(parallel=) for an explicit layout)',
                stacklevel=2,
            )

    if _state.initialized:
        if parallel is not None and not _same_plan(parallel, _state.plan):
            # The mesh (and any installed plan) is frozen at first init:
            # silently returning the OLD layout while the caller asked
            # for a new one would leave every plan consumer
            # (make_train_step(parallel=), loader defaults, manifests)
            # quietly plan-less or stale — be loud about it.
            warnings.warn(
                "fluxmpi_tpu is already initialized; init(parallel=) "
                "cannot rebuild the global mesh on an idempotent replay "
                "— the existing mesh/plan stays. Call shutdown() first "
                "to re-init under a different ParallelConfig.",
                stacklevel=2,
            )
        _configure_telemetry(telemetry)
        _tracing.configure(trace)
        _watchdog.configure(watchdog)
        _configure_preemption(preemption)
        _faults_mod.configure(faults)
        _goodput.configure(goodput)
        _anomaly.configure(anomaly)
        _modelstats.configure(model_stats)
        _compileplane.configure(compileplane)
        _memory.configure(memory)
        _profiling.configure_auto_profiler(profile)
        _configure_compile_cache(compile_cache)
        _export.configure(export)
        _serving.configure(serving)
        _serving_observe.configure(request_log)
        _fleet.configure(fleet)
        _resize.configure(resize)
        if auto_requested:
            _state.auto_parallel = True
        if verbose:
            fluxmpi_println("fluxmpi_tpu already initialized; skipping...")
        assert _state.mesh is not None
        return _state.mesh

    if distributed is None:
        distributed = coordinator_address is not None or _should_init_distributed()
    if distributed and not _state.distributed:
        # Must run before ANY backend use (jax.devices/process_count/...)
        # or the coordinator handshake cannot happen. A failure here must be
        # loud: silently degrading a pod slice to independent single-process
        # worlds would train without gradient sync and produce wrong results.
        try:
            # CPU worlds need the gloo collectives opt-in BEFORE the
            # backend client exists, or every cross-process device
            # computation fails with "Multiprocess computations aren't
            # implemented on the CPU backend" (no-op on TPU/GPU).
            from .parallel._compat import (
                enable_cpu_cross_process_collectives,
            )

            enable_cpu_cross_process_collectives()
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            _state.distributed = True
        except RuntimeError as e:  # pragma: no cover - deployment-specific
            if "already" in str(e).lower():
                _state.distributed = True
            else:
                raise

    if parallel is not None and mesh_shape is not None:
        raise ValueError(
            "pass either parallel= (the declarative plan) or mesh_shape= "
            "(an ad-hoc mesh), not both"
        )
    devs = list(devices) if devices is not None else jax.devices()
    if parallel is not None:
        from .parallel.plan import ParallelConfig, ResolvedPlan

        if isinstance(parallel, ResolvedPlan):
            plan = parallel
            if devices is not None:
                plan_devs = {d.id for d in plan.mesh.devices.flat}
                want = {d.id for d in devs}
                if plan_devs != want:
                    raise TopologyMismatchError(
                        f"init(devices=) names {len(want)} device(s) but "
                        f"the pre-resolved plan's mesh covers device ids "
                        f"{sorted(plan_devs)} — resolve the ParallelConfig "
                        f"against those devices, or pass the config "
                        f"itself"
                    )
        elif isinstance(parallel, ParallelConfig):
            plan = parallel.resolve(devs)
        else:
            raise ValueError(
                f"parallel= must be a ParallelConfig or ResolvedPlan, "
                f"got {parallel!r}"
            )
        mesh = plan.mesh
        _state.plan = plan
        axis_names = tuple(mesh.axis_names)
        sizes = [int(s) for s in mesh.shape.values()]
    else:
        if mesh_shape is None:
            mesh_shape = {config.DP_AXIS_NAME: len(devs)}
        axis_names = tuple(mesh_shape.keys())
        sizes = list(mesh_shape.values())
        if sizes.count(-1) > 1:
            raise ValueError(
                "at most one mesh axis may have inferred size -1"
            )
        if -1 in sizes:
            known = int(np.prod([s for s in sizes if s != -1]))
            if len(devs) % known != 0:
                raise ValueError(
                    f"cannot infer mesh axis: {len(devs)} devices not "
                    f"divisible by {known}"
                )
            sizes[sizes.index(-1)] = len(devs) // known
        if int(np.prod(sizes)) != len(devs):
            raise ValueError(
                f"mesh_shape {dict(zip(axis_names, sizes))} does not cover "
                f"{len(devs)} devices"
            )
        mesh = Mesh(np.asarray(devs).reshape(sizes), axis_names)
        _state.plan = None
    _state.mesh = mesh
    _state.initialized = True
    _state.auto_parallel = auto_requested
    _configure_telemetry(telemetry)
    _tracing.configure(trace)
    _watchdog.configure(watchdog)
    _configure_preemption(preemption)
    _faults_mod.configure(faults)
    _goodput.configure(goodput)
    _anomaly.configure(anomaly)
    _modelstats.configure(model_stats)
    _compileplane.configure(compileplane)
    _memory.configure(memory)
    _profiling.configure_auto_profiler(profile)
    _configure_compile_cache(compile_cache)
    _export.configure(export)
    _serving.configure(serving)
    _serving_observe.configure(request_log)
    # After export.configure: the collector's default scrape target is
    # this host's own live exporter when FLUXMPI_TPU_FLEET_HOSTS is
    # unset, so the exporter must already be resolved.
    _fleet.configure(fleet)
    _resize.configure(resize)
    if _state.plan is not None:
        # PARALLEL board: the resolved mesh/axis sizes land on /status
        # and the parallel.* gauges the moment the plan is installed
        # (rule hit counts follow from plan.shard_state).
        from .parallel.plan import post_board

        post_board(_state.plan)

    if verbose:
        if total_workers() == 1:
            warnings.warn(
                "Using fluxmpi_tpu with only 1 worker. It might be faster to "
                "run the code without the distributed wrappers.",
                stacklevel=2,
            )
        fluxmpi_println(
            f"Initialized: {jax.process_count()} process(es), "
            f"{len(devs)} device(s), mesh axes {dict(zip(axis_names, sizes))}, "
            f"platform {devs[0].platform}"
        )
    return mesh


def is_initialized() -> bool:
    """Has the runtime been initialized? (reference: src/common.jl:6)."""
    return _state.initialized


# Reference-spelling alias (``FluxMPI.Initialized``).
Initialized = is_initialized


def shutdown() -> None:
    """Reset runtime state (test helper; analogue of ``MPI.Finalize`` in the
    reference test files, e.g. test/test_common.jl:15). Disarms the
    watchdog, exports the trace ring (when a path was configured), and
    flushes/detaches any telemetry sinks so a final partial record is
    never lost — then drops the mesh. Ordered so the trace export still
    sees the process index. The fault-tolerance plane resets with the
    runtime too: a fault schedule or preemption flag left armed across an
    init/shutdown cycle would make the next run inject faults (or
    "preempt" at its first dispatch boundary) that nobody asked for."""
    try:
        from .telemetry import shutdown as _telemetry_shutdown

        _telemetry_shutdown()
    except Exception:
        pass
    try:
        from . import faults as _faults

        _faults.clear()
    except Exception:
        pass
    uninstall_preemption_handlers()
    _state.initialized = False
    _state.mesh = None
    _state.plan = None
    _state.auto_parallel = False


def _require_init() -> None:
    if not _state.initialized:
        raise FluxMPINotInitializedError()


def local_rank() -> int:
    """Rank of this controller process (reference: src/common.jl:52-57)."""
    _require_init()
    return jax.process_index()


def total_workers() -> int:
    """Total number of data-parallel workers — global device count
    (reference: src/common.jl:64-69; there 1 worker == 1 GPU == 1 process,
    here 1 worker == 1 TPU chip)."""
    _require_init()
    return int(np.prod(list(_state.mesh.shape.values())))  # type: ignore[union-attr]


def process_index() -> int:
    """Index of this controller process in the multi-host world."""
    _require_init()
    return jax.process_index()


def process_count() -> int:
    """Number of controller processes in the multi-host world."""
    _require_init()
    return jax.process_count()


def device_count() -> int:
    """Global device count."""
    _require_init()
    return jax.device_count()


def local_device_count() -> int:
    """Devices addressable by this process."""
    _require_init()
    return jax.local_device_count()


def global_mesh() -> Mesh:
    """The mesh built by :func:`init` — the analogue of ``MPI.COMM_WORLD``
    (reference passes the world comm to every collective,
    e.g. src/optimizer.jl:21, src/synchronize.jl:16)."""
    _require_init()
    assert _state.mesh is not None
    return _state.mesh


def global_plan() -> Any:
    """The :class:`~fluxmpi_tpu.parallel.plan.ResolvedPlan` installed by
    ``init(parallel=)``, or None (uninitialized runtime, or a mesh built
    from ``mesh_shape=``/defaults). Non-raising on purpose: consumers
    (pipeline/ring/ulysses axis-name defaults, checkpoint manifests)
    fall back to the ``*_axis_name`` preferences when no plan exists."""
    return _state.plan


def auto_parallel() -> bool:
    """Was the runtime armed with ``init(parallel="auto")`` (or
    ``FLUXMPI_TPU_PARALLEL=auto``)? While True and no autotuned plan is
    installed yet, :func:`global_plan` is still None — the layout
    autotuner fills it in."""
    return _state.initialized and _state.auto_parallel


def _install_autotuned_plan(plan: Any) -> bool:
    """Install the layout autotuner's winning plan as the global plan
    (and its mesh as the global mesh). Only honored under an armed auto
    mode on an initialized runtime — a hand-pinned init must never have
    its layout swapped out from under it. Returns True when installed."""
    if not _state.initialized or not _state.auto_parallel:
        return False
    from .parallel.plan import post_board

    _state.mesh = plan.mesh
    _state.plan = plan
    post_board(plan)
    return True


def dp_axis_name() -> str:
    """Name of the data-parallel mesh axis (the installed plan's when
    ``init(parallel=)`` built the mesh, else the preference)."""
    if _state.plan is not None:
        return _state.plan.dp_axis_name
    return config.DP_AXIS_NAME
