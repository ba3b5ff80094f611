"""Profiling / tracing helpers.

The reference has no tracing subsystem (SURVEY.md §5: only wall-clock
deltas in example scripts). On TPU the JAX profiler is nearly free to
expose: :func:`profile_trace` captures an XPlane trace viewable in
TensorBoard/Perfetto; :func:`step_timer` gives honest step timings around
async dispatch (blocks on results — the ``MPI.Waitall!`` of timing).

:class:`AutoProfiler` turns the XPlane capture into a *triggered*
instrument: armed via ``FLUXMPI_TPU_PROFILE_DIR`` (or
``init(profile=...)``), it captures one bounded-duration profiler window
when the anomaly detector fires a ``step_time_regression`` or
``steady_state_retrace`` (see :mod:`fluxmpi_tpu.telemetry.anomaly`) or
on ``SIGUSR2`` — so the evidence for a live perf regression is on disk
before a human opens a terminal. Captures are rate-limited (default:
once per run) because a regressing run would otherwise re-trigger at
every flush and profile itself to death.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Any, Iterator

import jax

__all__ = [
    "profile_trace",
    "step_timer",
    "AutoProfiler",
    "get_auto_profiler",
    "set_auto_profiler",
    "maybe_auto_capture",
    "configure_auto_profiler",
    "shutdown_auto_profiler",
]

_ENV_PROFILE_DIR = "FLUXMPI_TPU_PROFILE_DIR"
_ENV_PROFILE_SECONDS = "FLUXMPI_TPU_PROFILE_SECONDS"
_ENV_PROFILE_LIMIT = "FLUXMPI_TPU_PROFILE_LIMIT"


def _per_process_dir(logdir: str) -> str:
    """Each process's private capture directory under a shared logdir:
    ``<logdir>/proc<k>`` in a multi-process world (the XPlane writers
    otherwise collide on the shared path), the plain logdir when
    single-process (no surprise nesting)."""
    if jax.process_count() > 1:  # pragma: no cover - multihost only
        return os.path.join(logdir, f"proc{jax.process_index()}")
    return logdir


#: The ring's export of a capture, beside the profiler's own files.
SPANS_FILE = "fluxmpi_spans.trace.json"


@contextlib.contextmanager
def profile_trace(
    logdir: str, *, all_hosts: bool = False, profiler_options: Any = None
) -> Iterator[None]:
    """Capture a profiler trace of the enclosed block into ``logdir``.

    By default only the lead process traces — device activity is
    mirrored across DP replicas, so one host's XPlane is usually the
    whole picture. Pass ``all_hosts=True`` to trace on every process
    (straggler hunts, where the point is comparing hosts); each process
    then writes into its own ``<logdir>/proc<k>`` subdirectory
    automatically, so one shared logdir (GCS bucket, NFS path) works —
    the writers no longer collide.

    The program's own spans (:func:`fluxmpi_tpu.telemetry.tracing.span`)
    reach the capture through two channels. While the session records
    host events they are ``TraceAnnotation`` events on the XPlane's host
    plane, on the device trace's clock. A capture that drops host events
    (``profiler_options.host_tracer_level = 0``, for a host-fed loop the
    runtime's own TraceMe spans would slow) comes back with an empty
    host plane, so the span ring is enabled for the capture's length and
    its export written beside the XPlane as ``<dir>/fluxmpi_spans.trace.json``
    (wall-clock microseconds; the XPlane's ``profile_start_time`` is the
    same clock). A ring that was already recording is left recording.

    View with TensorBoard's profile plugin or Perfetto.
    """
    from ..telemetry import tracing

    if all_hosts:
        target = _per_process_dir(logdir)
    elif jax.process_index() == 0:
        target = logdir
    else:  # pragma: no cover - multihost only
        yield
        return
    options = (
        {} if profiler_options is None
        else {"profiler_options": profiler_options}
    )
    tracer = tracing.get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True
    try:
        with jax.profiler.trace(target, **options):
            yield
    finally:
        tracer.enabled = was_enabled
        try:
            tracer.export(os.path.join(target, SPANS_FILE))
        except OSError as exc:  # a logdir only the profiler can write (gs://)
            print(
                f"fluxmpi_tpu: span ring not exported beside the capture "
                f"in {target!r}: {exc!r}",
                file=sys.stderr,
            )


class AutoProfiler:
    """Anomaly/signal-triggered XPlane capture with a per-run budget.

    Args:
      logdir: capture destination; every process writes into its own
        ``<logdir>/proc<k>`` subdirectory in a multi-process world (the
        :func:`profile_trace` collision contract). Each capture lands in
        the profiler's own timestamped subtree, so repeated captures
        coexist.
      seconds: bounded capture window. The capture runs on a daemon
        thread — ``start_trace`` now, ``stop_trace`` after the window —
        so the training loop keeps running *inside* the captured window
        (that running work IS the evidence).
      limit: automatic captures allowed per run (default 1 — a
        regressing run re-triggers at every flush; the first capture is
        the evidence, the rest would be overhead). ``SIGUSR2`` /
        ``force=True`` captures bypass the budget (a human asked), but
        never overlap a live capture.
    """

    def __init__(
        self,
        logdir: str,
        *,
        seconds: float = 3.0,
        limit: int = 1,
    ):
        if seconds <= 0:
            raise ValueError(f"seconds must be > 0, got {seconds}")
        if limit < 0:
            raise ValueError(f"limit must be >= 0, got {limit}")
        self.logdir = logdir
        self.seconds = float(seconds)
        self.limit = int(limit)
        self._lock = threading.Lock()
        self._captures = 0
        self._auto_captures = 0
        self._capturing = False
        self._thread: threading.Thread | None = None
        self._prev_sigusr2: Any = None
        self.last_capture_path: str | None = None
        self.last_reason: str | None = None

    @property
    def captures(self) -> int:
        """Captures started so far (auto + forced)."""
        return self._captures

    def reset(self) -> None:
        """Restore the automatic-capture budget (``train_loop`` calls
        this per run). Only the budget re-opens — :attr:`captures`
        stays a monotonic total of every window started."""
        with self._lock:
            self._auto_captures = 0

    def maybe_capture(self, reason: str, *, force: bool = False) -> str | None:
        """Start one bounded capture if the budget allows (``force``
        bypasses the budget, not the no-overlap rule). Returns the
        capture directory, or None when skipped. Non-blocking: the
        window closes on a daemon thread; :meth:`wait` joins it."""
        with self._lock:
            if self._capturing:
                return None
            if not force:
                # Only automatic triggers spend the budget — an early
                # SIGUSR2 must not eat the one capture a later anomaly
                # exists to write.
                if self._auto_captures >= self.limit:
                    return None
                self._auto_captures += 1
            self._captures += 1
            self._capturing = True
        logdir = _per_process_dir(self.logdir)
        thread = threading.Thread(
            target=self._capture,
            args=(logdir, not force),
            name="fluxmpi-autoprofile",
            daemon=True,
        )
        self.last_capture_path = logdir
        self.last_reason = reason
        self._thread = thread
        thread.start()
        return logdir

    def _capture(self, logdir: str, auto: bool) -> None:
        started = False
        try:
            jax.profiler.start_trace(logdir)
            started = True
            # Announce only an OPEN window — a premature success line
            # would send an operator to an empty directory when the
            # session failed to start.
            print(
                f"fluxmpi_tpu auto-profiler: capturing {self.seconds:g}s "
                f"XPlane window into {logdir} "
                f"(reason: {self.last_reason})",
                file=sys.stderr,
            )
            time.sleep(self.seconds)
        except Exception:  # the profiler must never kill the run
            pass
        finally:
            # Stop ONLY a session this thread started: if start_trace
            # failed because another profiler session is live (a user's
            # profile_trace), an unconditional stop would terminate
            # THEIR capture mid-window and crash their context exit.
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
            with self._lock:
                self._capturing = False
                if not started:
                    # Refund: a capture that never opened wrote nothing
                    # — the budget must stay available for the next
                    # trigger instead of ending the run evidence-less.
                    # Clamped: a reset() racing the stalled start must
                    # not underflow the budget into limit+1 captures.
                    self._captures = max(0, self._captures - 1)
                    if auto:
                        self._auto_captures = max(
                            0, self._auto_captures - 1
                        )
            if not started:
                print(
                    f"fluxmpi_tpu auto-profiler: capture into {logdir} "
                    f"failed to start (another profiler session live?); "
                    f"budget refunded",
                    file=sys.stderr,
                )

    def wait(self, timeout: float | None = None) -> None:
        """Join the in-flight capture window, if any (tests; shutdown)."""
        thread = self._thread
        if thread is not None:
            thread.join(timeout)

    # -- SIGUSR2 dump-on-demand (the watchdog's SIGUSR1 discipline) ----

    def _on_sigusr2(self, signum: int, frame: Any) -> None:
        # Signal handlers run between bytecodes on the main thread;
        # start_trace takes profiler-internal locks, so the handler only
        # spawns the capture thread (thread creation takes no user
        # locks) and returns.
        threading.Thread(
            target=self.maybe_capture,
            args=("signal",),
            kwargs={"force": True},
            daemon=True,
        ).start()

    def install_signal(self) -> None:
        """Install the SIGUSR2 capture-on-demand handler (main thread
        only; degrades silently elsewhere — the triggered path still
        works, only dump-on-demand is lost)."""
        import signal

        try:
            self._prev_sigusr2 = signal.signal(
                signal.SIGUSR2, self._on_sigusr2
            )
        except (ValueError, OSError, AttributeError):
            self._prev_sigusr2 = None

    def uninstall_signal(self) -> None:
        import signal

        if self._prev_sigusr2 is not None:
            try:
                signal.signal(signal.SIGUSR2, self._prev_sigusr2)
            except (ValueError, OSError):
                pass
            self._prev_sigusr2 = None


_auto: AutoProfiler | None = None


def get_auto_profiler() -> AutoProfiler | None:
    """The armed auto-profiler, if any (None = triggered capture off)."""
    return _auto


def set_auto_profiler(profiler: AutoProfiler | None) -> AutoProfiler | None:
    """Install (or, with None, remove) the process auto-profiler;
    returns the previous one. Signal handlers are the caller's business
    (``configure_auto_profiler`` installs them)."""
    global _auto
    prev, _auto = _auto, profiler
    return prev


def maybe_auto_capture(reason: str) -> str | None:
    """Trigger the armed auto-profiler (no-op returning None when none
    is armed) — what the anomaly detector calls on
    ``step_time_regression`` / ``steady_state_retrace``."""
    ap = _auto
    if ap is None:
        return None
    return ap.maybe_capture(reason)


def configure_auto_profiler(spec: Any = None) -> AutoProfiler | None:
    """Wire triggered profiling from a one-value spec (mirror of
    :func:`fluxmpi_tpu.telemetry.configure`):

    - ``None`` — read ``FLUXMPI_TPU_PROFILE_DIR`` (no-op when
      unset/empty); window seconds and the per-run capture limit come
      from ``FLUXMPI_TPU_PROFILE_SECONDS`` (default 3) and
      ``FLUXMPI_TPU_PROFILE_LIMIT`` (default 1);
    - ``False`` / ``"0"`` — disarm (restores SIGUSR2);
    - a path string — arm an :class:`AutoProfiler` at that logdir;
    - an :class:`AutoProfiler` — arm it.

    Arming installs the ``SIGUSR2`` capture-on-demand handler. Called by
    ``fluxmpi_tpu.init(profile=...)``; idempotent — a replay with the
    same logdir/window keeps the armed instance AND its spent capture
    budget (``init()`` replays must not grant a fresh budget)."""
    global _auto
    if spec is None:
        spec = os.environ.get(_ENV_PROFILE_DIR)
        if spec is None or spec == "":
            return _auto
    if spec is False or spec == "0":
        shutdown_auto_profiler()
        return None
    if isinstance(spec, AutoProfiler):
        if spec is _auto:
            return spec
        shutdown_auto_profiler()
        set_auto_profiler(spec)
        spec.install_signal()
        return spec
    if not isinstance(spec, str):
        raise ValueError(
            f"profile spec must be a logdir path, False/'0', or an "
            f"AutoProfiler; got {spec!r}"
        )
    seconds = float(os.environ.get(_ENV_PROFILE_SECONDS) or 3.0)
    limit = int(os.environ.get(_ENV_PROFILE_LIMIT) or 1)
    if (
        _auto is not None
        and _auto.logdir == spec
        and _auto.seconds == seconds
        and _auto.limit == limit
    ):
        return _auto  # idempotent init() replay
    shutdown_auto_profiler()
    ap = AutoProfiler(spec, seconds=seconds, limit=limit)
    set_auto_profiler(ap)
    ap.install_signal()
    return ap


def shutdown_auto_profiler() -> None:
    """Disarm the auto-profiler: wait out any live capture window,
    restore SIGUSR2, and forget the instance (capture budgets must not
    leak across init cycles — the fault-plane leak rule)."""
    global _auto
    ap = _auto
    if ap is None:
        return
    # start_trace itself can stall for seconds on a cold profiler
    # backend; give the window generous room before abandoning it.
    ap.wait(timeout=ap.seconds + 60.0)
    ap.uninstall_signal()
    _auto = None


# One cached jitted sentinel for step_timer's no-watch fallback. A fresh
# `jax.jit(lambda x: x + 1)` per call would be a NEW jit cache entry each
# time (lambda identity keys the cache), so every timed step would
# retrace — the drain itself would dirty the timing it exists to honor.
_sentinel_bump = None


def _bump_fn():
    global _sentinel_bump
    if _sentinel_bump is None:
        _sentinel_bump = jax.jit(lambda x: x + 1)
    return _sentinel_bump


class _TimerHandle:
    def __init__(self) -> None:
        self._watched: list[Any] = []

    def watch(self, tree: Any) -> Any:
        """Register outputs to block on before the clock stops (returns the
        tree for inline use: ``out = t.watch(step(...))``)."""
        self._watched.append(tree)
        return tree


@contextlib.contextmanager
def step_timer(
    result_holder: dict,
    key: str = "seconds",
    *,
    metric: str | None = None,
    registry: Any | None = None,
) -> Iterator[_TimerHandle]:
    """Time the enclosed block including async-dispatched device work.

    Register the block's outputs with ``handle.watch(out)`` so the timer
    blocks on them before stopping the clock (the ``MPI.Waitall!`` of
    timing). With nothing watched, a sentinel computation is enqueued per
    local device and blocked on — TPU executes programs in order per
    device, so this drains prior dispatched work.

    ``metric="train.step_seconds"`` additionally observes the elapsed
    time into a telemetry histogram of that name (on ``registry``, or the
    default :func:`fluxmpi_tpu.telemetry.get_registry` when omitted) —
    the bridge between this timing discipline and the metrics substrate.
    """
    handle = _TimerHandle()
    t0 = time.perf_counter()
    yield handle
    if handle._watched:
        jax.block_until_ready(handle._watched)
    else:
        import jax.numpy as jnp

        bump = _bump_fn()
        for d in jax.local_devices():
            bump(jax.device_put(jnp.zeros(()), d)).block_until_ready()
    elapsed = time.perf_counter() - t0
    result_holder[key] = elapsed
    if metric is not None:
        if registry is None:
            from ..telemetry import get_registry

            registry = get_registry()
        registry.histogram(metric).observe(elapsed)


def block_on(tree: Any) -> Any:
    """Block until every array in ``tree`` is ready (the timing analogue of
    ``MPI.Waitall!``, reference src/optimizer.jl:59). Returns the tree."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return tree
