"""Pipelined steady-state training driver.

:func:`make_train_step` compiles the math of a step; this module owns the
*dispatch discipline* around it. The naive loop

.. code-block:: python

    for batch in loader:
        state, loss = step(state, batch)
        loss.block_until_ready()        # or device_get for logging

serializes the host against the device every step: the host cannot
assemble batch N+1 or enqueue step N+1 until step N fully drains. JAX
dispatch is asynchronous precisely so that it doesn't have to — the same
insight behind PyTorch DDP's comm/compute overlap (Li et al., VLDB 2020)
and tf.data's pipelined input processing (Murray et al., VLDB 2021).

:func:`train_loop` keeps the device fed instead:

- **bounded in-flight window** — up to ``in_flight`` step dispatches are
  outstanding before the host blocks on the *oldest* one, so batch
  assembly, host→device transfer, and compiled execution overlap while
  host memory stays bounded;
- **multi-step dispatch** — a step built with ``scan_steps=K`` consumes
  ``[K]``-stacked super-batches (one dispatch drives K optimizer
  updates); the driver feeds it by wrapping a
  :class:`~fluxmpi_tpu.data.DistributedDataLoader` in
  :func:`~fluxmpi_tpu.data.scan_batches` automatically — the adapter the
  compiled multi-step path was missing;
- **one-program flush windows** (``fuse="window"``, auto-enabled) — when
  the loader's device-gather path is active, the whole window fuses into
  ONE AOT-compiled ``lax.scan`` program: batch gather from the
  device-resident dataset, ``flush_every`` optimizer updates, and the
  interval metric reduction all run on device with the train state
  donated as the carry — the host performs one dispatch and one tiny
  metrics transfer per window instead of per-batch gather+step dispatch
  pairs (docs/performance.md, "One-program windows");
- **flush-boundary instrumentation** — telemetry and watchdog hooks run
  every ``flush_every`` updates (and at the end), not per step: the
  steady state pays zero per-step host blocking for metrics, and the
  recorded numbers are interval aggregates over honestly-drained work;
- **run-health plane** — when the goodput tracker is enabled
  (``init(goodput=True)`` / ``FLUXMPI_TPU_GOODPUT=1``) the loop
  attributes its wall clock into the
  :mod:`~fluxmpi_tpu.telemetry.goodput` buckets (productive step,
  first-dispatch compile, data stall, checkpoint save/restore, resume,
  preemption drain) and records live MFU from XLA's operation count of
  the step (:mod:`~fluxmpi_tpu.utils.flops`); when an
  :class:`~fluxmpi_tpu.telemetry.AnomalyDetector` is installed
  (``init(anomaly=True)`` / ``FLUXMPI_TPU_ANOMALY=1``) each flush's
  loss/grad-norm/step-time is checked and a ``halt``-policy trigger
  drains and exits cleanly with ``summary["anomaly"]`` set. Both planes
  sit behind the PR 4 zero-cost-when-off contract: fully disabled, the
  hot loop performs no extra perf_counter reads and no registry
  lookups.

After warmup the per-update host cost is one dict-free dispatch (1/K of
one, under ``scan_steps=K``) — the steady-state hot-path contract (see
docs/performance.md, "The steady-state loop").
"""

from __future__ import annotations

import contextlib
import time
import warnings
from collections import deque
from typing import Any, Iterable

import jax
import numpy as np

from ..runtime import preemption_handlers_installed, preemption_requested
from ..telemetry import tracing as _tracing
from .train import _resolve_metrics

__all__ = ["train_loop"]


def _epoch_iter(batches: Any, scan_steps: int) -> Iterable[Any]:
    """One epoch's super-batch stream: loaders get the scan-stacking
    adapter; anything else is assumed to already yield what the step
    consumes (pre-stacked when ``scan_steps > 1``)."""
    from ..data import DistributedDataLoader, scan_batches

    if scan_steps > 1 and isinstance(batches, DistributedDataLoader):
        return scan_batches(batches, scan_steps)
    return iter(batches)


def _epoch_len(batches: Any, scan_steps: int) -> int | None:
    """Dispatches per epoch when the source has a known length (loaders
    under the scan adapter drop the ragged trailing group); None for
    plain generators."""
    try:
        n = len(batches)
    except TypeError:
        return None
    from ..data import DistributedDataLoader

    if scan_steps > 1 and isinstance(batches, DistributedDataLoader):
        return n // scan_steps
    return n


# What next() returns once the per-step path's source has run dry.
_SOURCE_DRY = object()


def _stall_timed(it: Any, gp: Any) -> Iterable[Any]:
    """Wrap an epoch iterator so the host wait for each batch lands in
    the goodput ``data_stall`` bucket (enabled-tracker path only — the
    off path iterates the source directly, paying nothing)."""
    clock = gp._clock
    while True:
        t0 = clock()
        try:
            batch = next(it)
        except StopIteration:
            return
        gp.add("data_stall", clock() - t0)
        yield batch


def _maybe_oom_forensics(exc: BaseException, registry: Any) -> None:
    """On an XLA ``RESOURCE_EXHAUSTED`` escaping the dispatch loop,
    write the HBM forensics bundle (live-array census, per-device
    stats, peak watermark, watchdog dump sections) before the caller
    re-raises — the record of what was resident must survive the
    process. Any other exception passes through untouched; error path
    only, so the fully-off hot loop never reaches this."""
    from ..telemetry import memory as _memory

    if not _memory.is_oom_error(exc):
        return
    try:
        path = _memory.write_oom_bundle(exc, registry=registry)
        warnings.warn(
            f"device RESOURCE_EXHAUSTED: OOM forensics bundle written to "
            f"{path} (live-array census + HBM watermark); see "
            f"docs/observability.md 'Device plane'",
            stacklevel=3,
        )
    except Exception as bundle_exc:  # forensics must never mask the OOM
        warnings.warn(
            f"OOM forensics bundle write failed: {bundle_exc!r}",
            stacklevel=3,
        )


def _fused_window_width(
    step: Any,
    batches: Any,
    flush_every: int,
    steps: int | None,
    scan_k: int,
    forced: bool,
) -> int:
    """Resolve the fused-window width for ``train_loop(fuse=...)``: the
    number of optimizer updates one compiled window program drives, or 0
    when the fused path cannot drive this (step, loader) pair. ``forced``
    (``fuse="window"``) raises naming the failing condition instead of
    falling back.

    The width is ``flush_every`` clamped to the epoch length (an epoch
    shorter than the flush interval fuses as one window per pass), and
    the epoch must divide into whole windows — a ragged trailing window
    would recompile every epoch."""
    from ..data import DistributedDataLoader

    def fail(reason: str) -> int:
        if forced:
            raise ValueError(f'fuse="window" unavailable: {reason}')
        return 0

    if not isinstance(batches, DistributedDataLoader):
        return fail("batches is not a DistributedDataLoader")
    if getattr(step, "__fluxmpi_window_meta__", None) is None:
        return fail(
            "the step carries no fused-window metadata — build it with "
            "make_train_step(style='auto')"
        )
    if not batches.fusible():
        return fail(
            "the loader's device-gather path is not active (needs an "
            "array-backed single-process dataset without transform=, "
            "within FLUXMPI_TPU_DEVICE_GATHER_MAX_BYTES, whole full "
            "batches per epoch)"
        )
    nb = len(batches)
    if nb < 1:
        return fail("the loader has no full batches")
    width = min(flush_every, nb)
    if nb % width:
        return fail(
            f"epoch of {nb} batches does not divide into flush_every="
            f"{flush_every} windows (width {width}) — pick a flush_every "
            f"that divides the epoch"
        )
    if not forced and steps is not None and steps % width:
        # Window dispatch quantizes the steps budget (round up to whole
        # windows, like scan_steps quantizes to scan groups). Forcing
        # fuse="window" opts into that documented rounding; AUTO must
        # not silently change how many updates `steps` means, so it
        # keeps the pipelined path for misaligned budgets. (Windows stay
        # on the `width` grid across resumes — the short realignment
        # window restores it — so alignment here is alignment always.)
        return 0
    if not forced and scan_k > 1 and (
        nb % scan_k or (steps is not None and steps % scan_k)
    ):
        # Same rule for the scan quantum: the pipelined path's
        # scan_batches adapter DROPS the ragged trailing scan group
        # ((nb // k) * k updates per epoch) and rounds a steps budget
        # UP to whole scan groups, while the fused window — which
        # sequences single updates itself — would train all nb batches
        # and stop on the window grid. AUTO must not silently change
        # what an epoch or a steps budget means for a scan_steps step;
        # forcing fuse="window" opts into the window quantization.
        return 0
    return width


def _batch_examples(batch: Any, scan_steps: int) -> int:
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves or not getattr(leaves[0], "ndim", 0):
        return 0
    shape = np.shape(leaves[0])
    if scan_steps > 1:  # leading axis is scan time, not data
        return int(shape[0]) * int(shape[1]) if len(shape) > 1 else 0
    return int(shape[0])


def train_loop(
    step: Any,
    state: Any,
    batches: Any,
    *,
    steps: int | None = None,
    epochs: int | None = None,
    scan_steps: int | None = None,
    in_flight: int = 2,
    flush_every: int = 50,
    fuse: Any = "auto",
    metrics: Any | None = None,
    checkpoint: Any | None = None,
    save_every: int | None = None,
    resume: bool = False,
) -> tuple[Any, dict[str, Any]]:
    """Drive a compiled train step over a batch source, pipelined.

    Args:
      step: the step from :func:`make_train_step` — plain or built with
        ``metrics=`` (the per-step instrumentation wrapper is bypassed in
        the hot loop; its registry/monitor/hook spec is honored at flush
        boundaries instead) or with ``scan_steps=K`` (detected from the
        step, see ``scan_steps``).
      state: the :class:`~fluxmpi_tpu.parallel.TrainState` to advance.
        With donation on (the default), buffers update in place and the
        passed-in state must not be reused.
      batches: a :class:`~fluxmpi_tpu.data.DistributedDataLoader` (re-
        iterated per epoch; wrapped in
        :func:`~fluxmpi_tpu.data.scan_batches` when the step scans) or
        any iterable of ready batches. A plain generator supports a
        single pass — asking for more (``epochs > 1``, or ``steps``
        beyond its length) raises once it runs dry.
      steps: total optimizer updates to run (whole dispatches: rounded up
        to the scan width). ``None`` = run ``epochs`` passes instead.
      epochs: passes over ``batches`` (default 1 when ``steps`` is None;
        with ``steps`` set, whichever budget hits first wins).
      scan_steps: updates per dispatch. Default: read from the step (the
        factory tags it); pass explicitly for steps built elsewhere. Must
        match how the step was compiled.
      in_flight: dispatched-but-undrained step calls to keep outstanding
        (0 = block every call — the pre-pipelined behavior). Each
        outstanding call holds one batch + one state generation live on
        device, so memory grows with the window.
      flush_every: updates between instrumentation flushes. A flush
        blocks on the newest outstanding result (draining the pipeline),
        records interval aggregates, and ticks the watchdog — the ONLY
        places this driver blocks besides the final drain. Under
        ``fuse="window"`` this is also the window width (clamped to the
        epoch length): every window boundary is a flush boundary.
      fuse: ``"auto"`` (default) engages **one-program flush windows**
        when the loader's device-gather path is active and the epoch
        divides into ``flush_every``-update windows: batch gather, the
        window's optimizer updates, and the interval metric reduction
        (loss last/sum/max, grad-norm) are traced into ONE compiled
        ``lax.scan`` program per window — the host performs one dispatch
        and one tiny device→host metrics transfer per flush window
        instead of ``flush_every`` gather+step dispatch pairs. The train
        state is donated (carry updates in place in HBM) and the program
        is AOT-lowered (``jit(...).lower().compile()``) at loop start —
        booked into the goodput ``compile`` bucket, attributed by the
        compile monitor as ``train_loop.window``, and banked in the
        persistent compilation cache when one is wired
        (``init(compile_cache=)`` / ``FLUXMPI_TPU_COMPILE_CACHE``).
        ``"window"`` forces the fused path (raises naming the failing
        condition when ineligible); ``False``/``None`` keeps the
        pipelined per-batch path. Fused excludes what the device-gather
        path excludes — ``transform=``, generic/multi-process datasets,
        ragged epochs keep the host path — and metric/anomaly/preemption
        granularity moves to window boundaries (watchdog liveness too:
        the loop ticks once per window dispatch and once per flush, and
        the host blocks a full window draining it — size an armed
        watchdog's stall deadline above one window's wall time); a
        ``scan_steps`` tag on the step is subsumed (the window IS the
        scan), and ``steps`` budgets round up to whole windows —
        ``"auto"`` therefore keeps the pipelined path when ``steps`` is
        not a multiple of the window, or when a ``scan_steps`` step
        meets a ragged epoch its stacking adapter would have truncated,
        so it never silently changes how many updates a budget means.
        The resume contract is
        unchanged: a checkpoint cursor landing inside a window (a
        pipelined run's save, or an elastic remap) resumes with one
        shorter first window, sample-exact. See docs/performance.md,
        "One-program windows".
      metrics: same spec as :func:`make_train_step` (``True`` = default
        registry, a registry/monitor, or a callable receiving the
        interval record). ``None`` (default) inherits the spec the step
        was built with (``make_train_step(metrics=...)``), so an
        instrumented step keeps reporting — at flush granularity —
        without restating the spec here; ``False`` forces recording off
        either way (flushes then only tick the watchdog). Recorded per
        flush:
        ``train.step_seconds`` (histogram — MEAN seconds per update over
        the interval, honestly drained), ``train.loss`` /
        ``train.grad_norm`` (last value; grad-norm only for instrumented
        steps, whose compiled program carries it out),
        ``train.examples_per_sec``, cumulative ``train.steps`` /
        ``train.examples``.

      checkpoint: a :class:`~fluxmpi_tpu.utils.CheckpointManager` that
        owns this run's fault-tolerance: periodic saves (``save_every``),
        the preemption emergency save, and ``resume``. Each save banks a
        crash-consistent wrapper of the TrainState PLUS the loop
        counters and the loader position
        (:meth:`~fluxmpi_tpu.data.DistributedDataLoader.state_dict`), so
        a restart replays from the exact dispatch boundary — mid-epoch
        included (see docs/fault_tolerance.md).
      save_every: checkpoint every N optimizer updates (at dispatch
        boundaries; requires ``checkpoint``). ``None`` = no periodic
        saves (preemption still writes an emergency checkpoint when a
        manager is passed).
      resume: restore the newest committed checkpoint from
        ``checkpoint`` before training — state, loop counters, and
        loader position; an empty directory starts fresh, so the SAME
        command line is restart-proof. ``steps``/``epochs`` are TOTAL
        budgets: a run resumed at update 60 with ``steps=100`` runs 40
        more. Bumps the ``train.resumes`` counter.

        Elastic resume (docs/fault_tolerance.md, "Elastic resume"): the
        checkpoint's topology manifest is read first; when the world
        changed — different process count, mesh axis sizes, or loader
        global batch size — the banked loader cursor is remapped through
        its global sample offset (sample-exact: the resumed epoch
        consumes exactly the remaining samples; ragged remainders round
        down with the re-seen count logged), budgets keep their
        total-update/total-epoch meaning against the NEW per-epoch
        dispatch count, and the labeled
        ``train.resumes{topology_changed="true"}`` series ticks. The
        caller builds ``state`` for the CURRENT topology as usual —
        sharded leaves reshard through the manifest-validated orbax
        path, replicated ones root-broadcast. A checkpoint written
        before manifests existed resumes same-topology exactly as under
        PR 5 (with a warning).

    Preemption: when the runtime's preemption flag is set
    (``init(preemption=True)`` installs the SIGTERM/SIGINT handler; see
    :func:`fluxmpi_tpu.runtime.request_preemption`), the loop notices at
    the next dispatch boundary — multi-process runs coordinate the stop
    and so notice at the next ``flush_every`` boundary instead (the
    notice can land on different hosts at different dispatch counts;
    honoring it locally would desync collectives — size ``flush_every``
    to the preemption grace window, see docs/fault_tolerance.md) —
    drains the in-flight window, flushes instrumentation, writes an
    emergency checkpoint (when ``checkpoint`` is passed), and returns
    cleanly with ``summary["preempted"] = True`` — a
    ``train.preemption`` instant lands on the trace timeline.

    Live resize: with the resize plane armed (``init(resize=...)`` /
    ``FLUXMPI_TPU_RESIZE``) and a ``checkpoint`` attached, each flush
    boundary also polls :mod:`fluxmpi_tpu.fleet.resize` — a
    ``request_resize(M)`` on ANY process is agreed world-wide by one
    host max-reduce (the coordinated-preemption pattern), after which
    the loop drains, banks a final checkpoint (waiting out any
    in-flight async save), writes the resize handoff stamp next to it,
    and returns with ``summary["resized_to"] = M``. Relaunching under M
    processes with ``resume=True`` reshards via the topology manifest
    (sample-exact, the elastic-resume contract), stitches the
    drain/save/reshard/restart badput record
    (``fluxmpi_tpu.resize/v1``), and continues. See
    docs/fault_tolerance.md, "Zero-downtime ops".

    Device plane: with a
    :class:`~fluxmpi_tpu.telemetry.CompileMonitor` installed
    (``init(compileplane=True)`` / ``FLUXMPI_TPU_COMPILEPLANE=1``) the
    loop tags its hot step for retrace attribution and syncs
    ``compile.*`` metrics at every flush; compile events after the
    first flush (the warmup boundary) feed the anomaly detector's
    ``steady_state_retrace`` rule with the recompiled function's name —
    and, when the auto-profiler is armed (``FLUXMPI_TPU_PROFILE_DIR``),
    trigger a bounded XPlane capture. An XLA ``RESOURCE_EXHAUSTED``
    escaping the dispatch loop writes the ``fluxmpi_oom.<proc>.json``
    forensics bundle (live-array census, per-device HBM stats, peak
    watermark, watchdog dump sections) before re-raising. See
    docs/observability.md, "Device plane".

    Run health: with the goodput tracker enabled (``init(goodput=True)``
    / ``FLUXMPI_TPU_GOODPUT=1``) the loop attributes wall time into the
    :mod:`~fluxmpi_tpu.telemetry.goodput` buckets and records live
    ``goodput.*`` metrics (MFU included) at every flush; with an
    anomaly detector installed (``init(anomaly=True)`` /
    ``FLUXMPI_TPU_ANOMALY=1``) each flush's loss / grad-norm /
    step-time feeds its rules — a ``halt``-policy trigger (NaN loss by
    default) drains the window, skips further checkpoint saves (the
    last periodic save holds the last known-good state), and returns
    cleanly with ``summary["anomaly"]`` naming the rule, a diagnostics
    bundle on disk. Fully disabled (the default), neither plane adds
    perf_counter reads or registry lookups to the hot loop.

    Model internals: when the model-stats plane is on
    (``init(model_stats=True)`` / ``FLUXMPI_TPU_MODEL_STATS=1``) and the
    step was built while it was (the tree is part of the compiled
    program), every flush transfers the small per-layer stats tree and
    emits the ``model.*`` namespace — per-layer gradient/parameter
    norms, update-to-weight ratios, nonfinite counts (NaN provenance on
    the ``nan_grad``/``nan_loss`` anomaly events), and the gradient
    noise scale on shard_map steps. Identical on the pipelined and
    fused-window paths (the window program folds the tree into its scan
    carry). See docs/observability.md, "Model internals".

    Live export: with the exporter serving (``init(export=...)`` /
    ``FLUXMPI_TPU_EXPORT_PORT``) the loop posts its status board —
    run config at start, updates/loss/step-time per flush, the outcome
    at exit — to the ``/status`` endpoint, and ``/metrics`` scrapes see
    every flush's registry state live (see docs/observability.md,
    "Live export"). Off (the default), the loop reads one module
    attribute per run and never touches the exporter.

    Returns:
      ``(final_state, summary)`` — summary has ``updates``, ``epochs``,
      ``examples``, ``seconds``, ``updates_per_sec``,
      ``examples_per_sec``, final ``loss``, ``preempted``,
      ``resumed_from`` (the checkpoint step resumed from, else None),
      ``anomaly`` (the halting rule, else None), ``dispatches`` (host
      dispatches of the compiled program — ``dispatches/updates`` is
      the per-update host cost the fused path shrinks),
      ``fused_window`` (the engaged window width, else None), and —
      goodput enabled only — ``goodput`` (the tracker's
      :meth:`~fluxmpi_tpu.telemetry.GoodputTracker.report`).
    """
    from ..data import DistributedDataLoader
    from ..telemetry.watchdog import notify_progress

    if in_flight < 0:
        raise ValueError(f"in_flight must be >= 0, got {in_flight}")
    if flush_every < 1:
        raise ValueError(f"flush_every must be >= 1, got {flush_every}")
    if steps is not None and steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if save_every is not None and save_every < 1:
        raise ValueError(f"save_every must be >= 1, got {save_every}")
    if save_every is not None and checkpoint is None:
        raise ValueError("save_every requires a checkpoint= manager")
    if resume and checkpoint is None:
        raise ValueError("resume=True requires a checkpoint= manager")
    if steps is None and epochs is None:
        epochs = 1

    k = scan_steps if scan_steps is not None else getattr(step, "scan_steps", 1)
    if k < 1:
        raise ValueError(f"scan_steps must be >= 1, got {k}")

    # The hot loop calls the compiled program directly; a metrics= wrapper
    # from make_train_step would block per step, which is exactly what this
    # driver exists to avoid. Its compiled half returns (state, (loss,
    # grad_norm)) — handled uniformly below via tree leaves. (NOT
    # __wrapped__: jax.jit sets that too, to the *uncompiled* function.)
    hot = getattr(step, "__fluxmpi_compiled__", step)

    fused_w = 0
    if fuse not in (False, None):
        if fuse not in ("auto", "window"):
            raise ValueError(
                f'fuse must be "auto", "window", False, or None; '
                f"got {fuse!r}"
            )
        fused_w = _fused_window_width(
            hot, batches, flush_every, steps, k, forced=fuse == "window"
        )
    orig_k = k
    if fused_w:
        # The window program sequences single updates itself: the step's
        # scan_steps tag (and the stacking adapter) are bypassed, and
        # budgets / checkpoint cursors quantize to batches, not scan
        # groups.
        k = 1

    if metrics is None:
        # Honor the spec the step was built with (docstring contract):
        # unwrapping the per-step instrumentation must not silently drop
        # its registry/monitor/hook — they move to flush boundaries.
        metrics = getattr(step, "__fluxmpi_metrics__", None)
    reg, monitor, hook = (None, None, None)
    record_metrics = metrics is not None and metrics is not False
    if record_metrics:
        reg, monitor, hook = _resolve_metrics(metrics)
    from .. import comm as _comm
    from ..telemetry import get_registry
    from ..telemetry import anomaly as _anomaly
    from ..telemetry import compileplane as _compileplane
    from ..telemetry import export as _export
    from ..telemetry import fleet as _fleet
    from ..telemetry import goodput as _goodput
    from ..telemetry import modelstats as _modelstats
    from ..fleet import resize as _resize
    from .train import _DEFAULT_REGISTRY

    # Run-health + device planes, resolved ONCE per run (the
    # zero-cost-when-off contract: with all disabled the hot loop below
    # branches on three local bools — no perf_counter reads, no registry
    # lookups, no context managers, no monitoring subscriptions).
    # Enablement is env/init-driven, hence SPMD-consistent; halt
    # decisions are made at flush boundaries every process reaches at
    # the same updates count, from SPMD-consistent signals (see
    # telemetry/anomaly.py on policies).
    gp = _goodput.get_goodput_tracker()
    gp_on = gp.enabled
    detector = _anomaly.get_anomaly_detector()
    det_on = detector is not None and detector.enabled
    cp = _compileplane.get_compile_monitor()
    cp_on = cp is not None and cp.enabled
    # Live export plane: when an exporter is serving, the loop posts its
    # status board at flush boundaries (run config at start, counters /
    # loss per flush, outcome at exit) — a dict update under a lock, no
    # device syncs, nothing per step. Off (the default) the loop never
    # calls note_status (monkeypatch-explode tested).
    exporter = _export.get_exporter()
    exp_on = exporter is not None and exporter.enabled
    # Model-internals plane: the stats tree is baked into the compiled
    # program at build time (make_train_step(model_stats=)); the loop's
    # job is flush-boundary consumption — ONE device→host copy of the
    # small per-layer tree per flush, riding the drain the flush already
    # pays. On when the plane is installed AND the step actually carries
    # the tree; fully off, this is one module attribute read per run.
    ms = _modelstats.get_model_stats()
    ms_aux = getattr(hot, "__fluxmpi_aux__", None)
    ms_meta = getattr(hot, "__fluxmpi_model_stats_meta__", None)
    ms_on = (
        ms is not None
        and ms.enabled
        and ms_meta is not None
        and ms_aux is not None
        and "model_stats" in ms_aux
    )
    # Fleet plane: when armed (init(fleet=)/FLUXMPI_TPU_FLEET — SPMD-
    # consistent like the others), each flush posts this host's
    # cumulative attribution ingredients to its own /status board for
    # the cross-host collector to scrape. Rides the exporter (no
    # exporter, nothing to scrape), costs one dict merge per flush,
    # nothing per step; fully off it is one module attribute read here.
    fl_on = exp_on and _fleet.enabled()
    # Live-resize plane: when armed (init(resize=)/FLUXMPI_TPU_RESIZE —
    # SPMD-consistent like the others) AND a checkpoint manager is
    # attached (there is nothing to hand off otherwise), each flush
    # polls the coordinator's request flag exactly like coordinated
    # preemption: one host max-reduce of the target world size, so any
    # process's request_resize() enrolls the whole world at the SAME
    # update count. Off, this is one module attribute read per run.
    rz = _resize.get_resize_coordinator()
    rz_on = rz.enabled and checkpoint is not None
    resize_to: int | None = None
    if cp_on:
        # Tag the hot step for retrace attribution: its jit-cache growth
        # after the warmup boundary names it in the steady_state_retrace
        # event. The first flush IS the warmup boundary (observe_flush
        # marks it), so first-dispatch compiles never fire the rule.
        # One run window per train_loop (the goodput reset_run
        # discipline): without it a SECOND loop in the same process
        # would inherit run 1's steady-state mark and report its own
        # legitimate warmup compiles as retraces.
        cp.track("train_loop.step", hot)
        if fused_w:
            # The fused path dispatches AOT executables, which never
            # grow a jit cache — attribution and steady-state retrace
            # detection come from explicit note_aot_compile() calls at
            # lower() time instead.
            cp.track_aot("train_loop.window")
        cp.reset_run()
    if det_on:
        # The anomaly-triggered auto-profiler budgets captures PER RUN
        # (the documented contract): re-open it alongside the goodput
        # and compile run windows. Detector-gated — triggers only come
        # through the detector, so the off path reads nothing.
        from ..utils.profiling import get_auto_profiler

        auto_profiler = get_auto_profiler()
        if auto_profiler is not None:
            auto_profiler.reset()
    halt_rule: str | None = None
    if gp_on:
        # One tracker window per train_loop run: without the reset, a
        # second loop in the same process would inherit the first run's
        # buckets, book the gap between runs as host_idle, and compute
        # MFU from the FIRST step function's FLOPs.
        gp.reset_run()
        gp.start_run()  # anchor the wall clock before resume bring-up

    # Multi-process preemption coordination polls only when it could
    # matter (signal handlers installed, or a checkpoint to bank into) —
    # an unconditional per-flush host collective would tax runs that
    # never asked for preemption handling. checkpoint-presence is
    # SPMD-consistent by construction; handler state is NOT guaranteed to
    # be (install_preemption_handlers degrades to a warning off the main
    # thread), so the gate is agreed ONCE via a host max-reduce — any
    # process with handlers enrolls every process, and no process ever
    # skips a per-flush collective its peers run.
    multi = jax.process_count() > 1
    coordinate = multi and (
        checkpoint is not None
        or bool(
            _comm.host_allreduce(
                np.int32(preemption_handlers_installed()), op="max"
            )
        )
    )

    window: deque = deque()  # outstanding step outputs, oldest first
    updates = 0
    examples = 0
    epochs_done = 0
    dispatches = 0  # host dispatches of the hot/window program
    interval_updates = 0
    interval_examples = 0
    interval_windows = 0  # fused mode: windows since the last flush
    last_out: Any = None
    last_width = fused_w  # fused mode: width of the last window

    def _live_registry() -> Any:
        return get_registry() if reg is _DEFAULT_REGISTRY else reg

    # ---- fault-tolerance plane: checkpoint payloads, resume ----------
    is_loader = isinstance(batches, DistributedDataLoader)
    per_epoch = _epoch_len(batches, k)

    def _payload(
        st: Any, *, pass_counted: bool = False, legacy_loader: bool = False
    ) -> dict[str, Any]:
        # What a checkpoint banks: the TrainState plus everything the
        # loop needs to continue EXACTLY — cumulative counters and the
        # loader's (epoch, cursor) position. Scalars ride as int64
        # arrays so they survive the orbax round trip. The banked epoch
        # count is CANONICAL: it includes the current pass whenever the
        # cursor sits at the end of the epoch. In-loop saves happen
        # before the loop's own pass increment (pass_counted=False, so
        # an exact end-of-pass boundary adds it here); the post-drain
        # emergency save happens after (pass_counted=True).
        epochs_banked = epochs_done
        loader_state = batches.state_dict() if is_loader else None
        if (
            loader_state is not None
            and not pass_counted
            and len(batches) > 0
            and loader_state["cursor"] >= len(batches)
        ):
            epochs_banked += 1
        if (
            loader_state is not None
            and pass_counted
            and k > 1
            and per_epoch
            and loader_state["cursor"] < len(batches)
            and loader_state["cursor"] // k >= per_epoch
        ):
            # Ragged-scan boundary at a post-drain save: every
            # dispatchable scan group of this pass ran (the ragged tail
            # never dispatches) and the pass is already in epochs_banked
            # — bank the NEXT epoch's start so resume doesn't replay the
            # empty remainder and count the pass a second time.
            loader_state = {
                **loader_state,
                "epoch": loader_state["epoch"] + 1,
                "cursor": 0,
            }
        payload: dict[str, Any] = {
            "state": st,
            "loop": {
                "updates": np.asarray(updates, np.int64),
                "examples": np.asarray(examples, np.int64),
                "epochs": np.asarray(epochs_banked, np.int64),
            },
        }
        if loader_state is not None:
            if not legacy_loader:
                # Bank the batch geometry the cursor's meaning depends on
                # next to the position, so an elastic resume under a
                # different process count / global batch size can remap
                # it (load_state_dict reads these keys; the save-time
                # manifest records a copy). legacy_loader builds the
                # PR 5 template shape for restoring pre-manifest
                # checkpoints, whose banked loader dict has no geometry.
                loader_state = {**loader_state, **batches.geometry()}
            payload["loader"] = {
                key: np.asarray(val, np.int64)
                for key, val in loader_state.items()
            }
        return payload

    resumed_from = None
    resume_offset = 0  # dispatches already done in a resumed partial epoch
    if resume:
      # Resume bring-up is restart badput (elastic resizes included):
      # the whole block — manifest read, restore, cursor remap — lands
      # in the goodput "resume" bucket; the nested checkpoint_restore
      # segment inside checkpoint.restore counts once (outermost wins).
      with gp.segment("resume") if gp_on else contextlib.nullcontext():
        # The manifest (the topology sidecar every PR 6 save writes)
        # tells us, BEFORE any bytes move, whether the checkpoint comes
        # from a different world — and whether it predates manifests, in
        # which case the restore template must use the PR 5 payload
        # shape (no loader-geometry keys to miss). Read+validated ONCE
        # here and passed through to restore (None included: "looked,
        # absent"), killing the former per-resume double read; managers
        # without read_manifest keep the old read-inside-restore path.
        manifest = None
        read_manifest = getattr(checkpoint, "read_manifest", None)
        if read_manifest is not None:
            manifest = read_manifest()
            restore_kwargs = {"manifest": manifest}
        else:
            restore_kwargs = {}
        # A pending resize handoff stamp means this resume IS the
        # reshard phase of a live resize: fire its chaos site, time the
        # restore, and stitch the cross-restart badput record once the
        # state is back.
        ckpt_dir = getattr(checkpoint, "directory", None)
        resize_stamp = (
            rz.maybe_begin_reshard(ckpt_dir)
            if rz_on and ckpt_dir is not None
            else None
        )
        t_reshard0 = time.perf_counter()
        try:
            ckpt_step, restored = checkpoint.restore(
                _payload(state, legacy_loader=manifest is None),
                **restore_kwargs,
            )
        except FileNotFoundError:
            restored = None  # empty directory: fresh start, same command
        except (TypeError, ValueError, KeyError):
            # Structure-mismatch family only (what orbax raises when the
            # template tree disagrees with the checkpoint) — injected
            # faults (FaultInjectedError) and I/O errors must propagate,
            # not trigger a blind second restore.
            if manifest is not None:
                raise
            # No manifest does not prove a PR 5 payload: a PR 6
            # checkpoint whose sidecar was lost/corrupted still banks
            # the geometry-carrying loader dict, and the legacy template
            # just mismatched its structure. Retry with the full shape
            # before declaring the checkpoint unrestorable.
            ckpt_step, restored = checkpoint.restore(
                _payload(state), **restore_kwargs
            )
        if restored is not None:
            state = restored["state"]
            updates = int(restored["loop"]["updates"])
            examples = int(restored["loop"]["examples"])
            epochs_done = int(restored["loop"]["epochs"])
            topology_changed = False
            if manifest is not None:
                from ..utils import manifest as _manifest_util

                topology_changed = _manifest_util.topology_changed(
                    manifest, mesh=getattr(batches, "mesh", None)
                )
                saved_geom = manifest.get("loader") or {}
                if is_loader and saved_geom:
                    geom = batches.geometry()
                    topology_changed = topology_changed or any(
                        key in saved_geom
                        and int(saved_geom[key]) != geom[key]
                        for key in ("process_count", "global_batch_size")
                    )
            if is_loader and "loader" in restored:
                batches.load_state_dict(
                    {key: int(val) for key, val in restored["loader"].items()}
                )
                if fused_w and fuse == "auto" and steps is not None:
                    # Same-geometry resumes keep updates ≡ cursor
                    # (mod width) — windows then land exactly on an
                    # aligned steps budget. An ELASTIC geometry remap
                    # breaks the congruence (cursor rescales, updates
                    # doesn't), and window boundaries would straddle
                    # the budget and overshoot it. AUTO's rule — never
                    # silently change what `steps` means — extends
                    # here: fall back to the pipelined path (restoring
                    # the step's own scan quantum for the reseat
                    # below); fuse="window" keeps the rounding opt-in.
                    pos0 = batches.resume_cursor
                    short_first = (fused_w - pos0 % fused_w) % fused_w
                    if (steps - updates - short_first) % fused_w:
                        fused_w = 0
                        k = orig_k
                        per_epoch = _epoch_len(batches, k)
                # load_state_dict normalized an end-of-epoch cursor away
                # (the banked epoch count already includes that pass —
                # _payload's canonical form); what remains is mid-epoch
                # dispatches already done.
                if k > 1 and batches.resume_cursor % k:
                    # An elastic remap can land mid-scan-group (same-
                    # topology saves always sit at dispatch boundaries);
                    # re-seat at the group boundary so the scan adapter's
                    # grouping keeps the uninterrupted run's phase — the
                    # few re-dispatched batches are the same round-down
                    # contract as the remap itself.
                    seat = batches.state_dict()
                    seat["cursor"] = (batches.resume_cursor // k) * k
                    batches.load_state_dict(seat)
                resume_offset = batches.resume_cursor // k
            resumed_from = ckpt_step
            if resize_stamp is not None:
                rz.complete(
                    ckpt_dir,
                    resize_stamp,
                    reshard_seconds=time.perf_counter() - t_reshard0,
                    to_processes=jax.process_count(),
                )
            if record_metrics:
                registry = _live_registry()
                if registry is not None:
                    # The unlabeled series counts every resume (the PR 5
                    # contract); the labeled one counts the elastic
                    # subset so dashboards can tell a plain restart from
                    # a fleet resize.
                    registry.counter("train.resumes").inc()
                    if topology_changed:
                        registry.counter(
                            "train.resumes", topology_changed="true"
                        ).inc()

    last_saved = updates
    preempted = False
    if exp_on:
        # Run config + resume position, posted once the resume block has
        # settled them (fused_w can still fall back during an elastic
        # resume above).
        exporter.note_status(
            phase="running",
            updates=updates,
            examples=examples,
            epochs=epochs_done,
            steps_budget=steps,
            epochs_budget=epochs,
            flush_every=flush_every,
            scan_steps=k,
            fused_window=fused_w or None,
            resumed_from=resumed_from,
            preempted=False,
            anomaly=None,
        )

    def _save_ckpt(pass_counted: bool = False) -> None:
        nonlocal last_saved
        checkpoint.save(updates, _payload(state, pass_counted=pass_counted))
        last_saved = updates

    def _post_dispatch(at_flush: bool) -> None:
        """Dispatch-boundary bookkeeping shared by the pipelined and
        fused paths, in commit order: flush (and honor a halt-policy
        anomaly), check the steps budget, bank the boundary, then honor
        a pending preemption (whose emergency save then has nothing
        left to write). In fused mode every window boundary is a flush
        boundary, so all of this runs once per window."""
        nonlocal done, preempted, resize_to
        if at_flush:
            flush()
            if halt_rule is not None:
                # An anomaly with a halt policy: stop at this flush
                # boundary (SPMD-consistent — every process reached
                # it at the same updates count and judged the same
                # global scalars) WITHOUT banking a checkpoint of
                # the now-suspect state; the last periodic save
                # holds the last known-good boundary.
                done = True
        if steps is not None and updates >= steps:
            done = True
        if (
            checkpoint is not None
            and save_every is not None
            and halt_rule is None
            and updates - last_saved >= save_every
        ):
            _save_ckpt()
        if multi:
            # Coordinated stop: a local break would leave the other
            # processes dispatching collectives this one never joins
            # (a hang), or desync the emergency save's step-agreement
            # guard. Every process reaches each flush boundary at
            # the SAME updates count, so one tiny host max-reduce of
            # the flag there picks a common stop step. An ungated
            # multi-process run never breaks locally — that would be
            # the hang; preemption there needs handlers/checkpoint.
            if coordinate and at_flush and bool(
                _comm.host_allreduce(
                    np.int32(preemption_requested()), op="max"
                )
            ):
                preempted = True
                done = True
        elif preemption_requested():
            preempted = True
            done = True
        if rz_on and at_flush and resize_to is None:
            # Same shape as the preemption poll: every process reaches
            # this flush at the same updates count, so a host max-reduce
            # of the requested target (0 = none) agrees one resize for
            # the whole world. rz_on requires a checkpoint, so multi
            # implies coordinate — no process skips the collective.
            target = rz.requested_target()
            if multi:
                target = int(
                    _comm.host_allreduce(np.int32(target), op="max")
                )
            if target:
                resize_to = target
                rz.begin(target, from_processes=jax.process_count())
                done = True

    lbs_fused = batches.local_batch_size if fused_w else 0
    gbs_fused = batches.global_batch_size if fused_w else 0

    def _aval_key(tree: Any) -> tuple:
        """Hashable (structure, shapes, dtypes) fingerprint of a pytree —
        the part of the cache key that makes a banked AOT executable
        safe to reuse. A jit cache keys on avals natively; an AOT
        executable checks nothing, so dispatching one compiled for a
        DIFFERENT dataset/state shape would crash (or worse)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return (
            treedef,
            tuple(
                (np.shape(leaf), str(getattr(leaf, "dtype", "?")))
                for leaf in leaves
            ),
        )

    flops_probed = False  # one cost-model probe per run, hit or miss
    # Per-run window-cache ledger: how many window programs this run
    # reused vs compiled, and the seconds the compiles cost. Surfaces in
    # the summary (``window_cache`` / ``window_compile_seconds``) so
    # autotune trials can PROVE a run was a pure cache hit instead of
    # inferring it from wall clock.
    window_compile = {"seconds": 0.0, "hits": 0, "misses": 0}

    def _window_program(
        width: int, cur_state: Any, staged: Any, perm: Any, avals: tuple
    ):
        """The compiled window program for ``width`` updates: built by
        :func:`~fluxmpi_tpu.parallel.train.make_window_program`,
        AOT-lowered (``lower().compile()``) ONCE up front — booked as
        goodput compile work and attributed by the compile monitor as
        ``train_loop.window`` — and cached on the step across
        train_loop runs (the persistent compilation cache, when wired,
        covers restarts and other hosts). Lowering reads only avals, so
        the live pre-dispatch state is safe to pass."""
        cache = getattr(hot, "__fluxmpi_window_cache__", None)
        if cache is None:
            cache = {}
            try:
                hot.__fluxmpi_window_cache__ = cache
            except (AttributeError, TypeError):  # pragma: no cover
                pass
        key = (width, lbs_fused) + avals
        prog = cache.get(key)
        if prog is None:
            from .train import make_window_program

            fn = make_window_program(hot, width=width, lbs=lbs_fused)
            t0 = time.perf_counter()
            if gp_on:
                with gp.segment("compile"):
                    prog = fn.lower(
                        cur_state, staged, perm, np.int32(0)
                    ).compile()
            else:
                prog = fn.lower(
                    cur_state, staged, perm, np.int32(0)
                ).compile()
            dt = time.perf_counter() - t0
            window_compile["misses"] += 1
            window_compile["seconds"] += dt
            if cp_on:
                cp.note_aot_compile("train_loop.window", dt)
            cache[key] = prog
        else:
            window_compile["hits"] += 1
        nonlocal flops_probed
        if gp_on and not flops_probed and gp._flops_per_update is None:
            # FLOPs per update from the window executable's cost model —
            # the same accounting the pipelined path gets from
            # cost_analysis_flops, so live MFU is path-independent. On
            # the CACHE-HIT path too: reset_run() cleared the per-run
            # FLOPs, and a second run reusing the banked executable must
            # still report MFU. One probe per run either way — a backend
            # whose cost model reports no FLOPs must not be re-asked
            # every window.
            flops_probed = True
            from ..utils.flops import executable_flops

            flops = executable_flops(prog)
            if flops:
                gp.set_flops_per_update(flops / width)
        return prog

    t_start = time.perf_counter()
    t_flush = t_start

    # Per-interval delta base for the goodput data_stall bucket — what
    # the anomaly data-stall rule compares against the interval's step
    # time (per-update loader wait needs goodput enabled to exist).
    stall_base = gp.bucket_seconds("data_stall") if gp_on else 0.0

    def flush() -> None:
        nonlocal interval_updates, interval_examples, interval_windows
        nonlocal t_flush, halt_rule, stall_base
        if interval_updates == 0:
            return
        # The blocking boundary, one span: the drain to the newest result
        # and the device->host read of the interval's scalars.
        with _tracing.span(
            "loop.flush", update=updates, fused=bool(fused_w)
        ):
            if last_out is not None:
                # Drain to the newest dispatched result so the interval's wall
                # time covers completed work, not enqueued promises — the
                # step_timer discipline at flush granularity. The drain is
                # honest device compute: productive goodput.
                if gp_on:
                    with gp.segment("step"):
                        jax.block_until_ready(last_out)
                else:
                    jax.block_until_ready(last_out)
            now = time.perf_counter()
            elapsed = now - t_flush
            per_update = elapsed / interval_updates
            notify_progress(interval_updates)
            loss_v: float | None = None
            grad_v: float | None = None
            stats_host: Any = None
            window_stats: dict[str, float] = {}
            if record_metrics or det_on or exp_on or ms_on:
                if fused_w:
                    # The window program's metric carry: a dict of f32
                    # scalars (plus the model-stats tree when the plane is
                    # on) — ONE tiny device→host transfer per flush.
                    vals = jax.device_get(last_out)
                    loss_v = float(np.asarray(vals["loss"]))
                    if "grad_norm" in vals:
                        grad_v = float(np.asarray(vals["grad_norm"]))
                    if ms_on:
                        stats_host = vals.get("model_stats")
                    if last_width > 0:
                        window_stats["loss_window_mean"] = (
                            float(np.asarray(vals["loss_sum"])) / last_width
                        )
                    window_stats["loss_window_max"] = float(
                        np.asarray(vals["loss_max"])
                    )
                else:
                    if ms_on:
                        # Aux is (loss, grad_norm, stats): pull the whole
                        # tuple across in one transfer; a scan_steps step
                        # stacks each leaf [K] — the flush describes the
                        # NEWEST update, so take the last entry.
                        vals = jax.device_get(last_out)
                        loss_v = float(np.asarray(vals[0]).mean())
                        grad_v = float(np.asarray(vals[1]).mean())
                        stats_host = vals[2]
                        if k > 1:
                            from .train import _last_scan_entry

                            stats_host = _last_scan_entry(stats_host)
                    else:
                        leaves = jax.tree_util.tree_leaves(last_out)
                        loss_h = (
                            np.asarray(jax.device_get(leaves[0]))
                            if leaves else None
                        )
                        loss_v = (
                            float(loss_h.mean()) if loss_h is not None else None
                        )
                        if len(leaves) > 1:
                            grad_v = float(
                                np.asarray(jax.device_get(leaves[1])).mean()
                            )
        if record_metrics:
            record: dict[str, Any] = {
                "step_seconds": per_update,
                "steps": interval_updates,
                "examples": interval_examples,
                "examples_per_sec": (
                    interval_examples / elapsed if elapsed > 0 else 0.0
                ),
                "loss": loss_v,
            }
            if grad_v is not None:
                record["grad_norm"] = grad_v
            record.update(window_stats)
            registry = _live_registry()
            if registry is not None:
                registry.histogram("train.step_seconds").observe(per_update)
                if record["loss"] is not None:
                    registry.gauge("train.loss").set(record["loss"])
                if "grad_norm" in record:
                    registry.gauge("train.grad_norm").set(record["grad_norm"])
                registry.gauge("train.examples_per_sec").set(
                    record["examples_per_sec"]
                )
                registry.counter("train.steps").inc(interval_updates)
                registry.counter("train.examples").inc(interval_examples)
                if fused_w:
                    # The fused path's host-cost contract, observable in
                    # the JSONL stream: windows dispatched and the width
                    # each one fused.
                    registry.gauge("train.window.size").set(float(fused_w))
                    registry.counter("train.window.dispatches").inc(
                        interval_windows
                    )
            if monitor is not None:
                monitor.observe_step(per_update)
            if hook is not None:
                hook(record)
        fetch_per_update: float | None = None
        if gp_on:
            stall = gp.bucket_seconds("data_stall")
            fetch_per_update = (stall - stall_base) / interval_updates
            stall_base = stall
            # goodput.* gauges ride the same flush line as train.*.
            gp.record(_live_registry() if record_metrics else None)
        retraces: int | None = None
        retraced: str | None = None
        if cp_on:
            # Device plane: sync compile.* metrics, poll tagged jit
            # caches, cross-check the goodput compile bucket. The first
            # flush marks the warmup boundary; compile events on any
            # later flush are steady-state retraces handed to the
            # detector with the recompiled function's name.
            info = cp.observe_flush(
                _live_registry() if record_metrics else None,
                goodput_tracker=gp if gp_on else None,
            )
            if info["steady"] and info["events"]:
                retraces = info["events"]
                retraced = ",".join(info["functions"])
        msum: dict[str, Any] | None = None
        if ms_on and stats_host is not None:
            # Emit the model.* namespace and fold the per-layer view
            # into one summary for the detector and the status board.
            # The noise-scale ingredients (shard_map steps) divide by
            # the per-update batch, identical on both drivers.
            msum = ms.observe_flush(
                stats_host,
                step=updates,
                registry=_live_registry() if record_metrics else None,
                batch_examples=(
                    interval_examples / interval_updates
                    if interval_updates else None
                ),
                workers=ms_meta.get("workers"),
            )
        if det_on:
            events = detector.observe(
                loss=loss_v,
                grad_norm=grad_v,
                step_seconds=per_update,
                fetch_seconds=fetch_per_update,
                retraces=retraces,
                retraced=retraced,
                layer_grad_norms=msum["layers"] if msum else None,
                nonfinite_layer=(
                    msum["nonfinite_layer"] if msum else None
                ),
                step=updates,
            )
            for ev in events:
                if ev["action"] == "halt" and halt_rule is None:
                    halt_rule = ev["rule"]
        if exp_on:
            # /status stays current between JSONL flushes: the numbers
            # this flush just drained, posted to the live status board.
            exporter.note_status(
                updates=updates,
                examples=examples,
                epochs=epochs_done,
                loss=loss_v,
                grad_norm=grad_v,
                step_seconds=per_update,
                examples_per_sec=(
                    interval_examples / elapsed if elapsed > 0 else 0.0
                ),
                dispatches=dispatches,
            )
            if fl_on:
                # The FLEET board: cumulative attribution ingredients
                # the cross-host collector deltas per scrape interval
                # to name the straggler and its cause — goodput
                # buckets when that plane is on (data stall vs compute
                # vs idle), the comm layer's cumulative collective
                # block time (comm_wait), and the flight-recorder
                # launch sequence (frozen while peers advance =
                # desync). All cumulative: the collector owns the
                # windowing, so scrape and flush cadences need not
                # align.
                from ..telemetry.flight_recorder import (
                    get_flight_recorder,
                )

                fr = get_flight_recorder()
                comm_total = 0.0
                for m in get_registry().snapshot():
                    if m.get("name") == "comm.block_seconds":
                        comm_total += float(m.get("sum", 0.0))
                fleet_fields: dict[str, Any] = {
                    "updates": updates,
                    "flight_seq": float(fr.sequence),
                    "flight_completed": float(fr.completed_count),
                    "comm_block_seconds": comm_total,
                }
                if gp_on:
                    rep = gp.report()
                    fleet_fields["wall_seconds"] = rep["wall_seconds"]
                    for bucket in ("step", "data_stall", "host_idle"):
                        fleet_fields[f"{bucket}_seconds"] = rep[
                            "buckets"
                        ].get(bucket, 0.0)
                exporter.note_fleet(**fleet_fields)
            if msum is not None:
                # The MODEL board: noise scale, top-k layers by grad
                # norm, and NaN provenance — what fluxmpi_top renders.
                exporter.note_model(
                    step=updates,
                    noise_scale=msum["noise_scale"],
                    nonfinite_layer=msum["nonfinite_layer"],
                    top=[
                        {"layer": layer, "grad_norm": gnorm}
                        for layer, gnorm in msum["top"]
                    ],
                )
        interval_updates = 0
        interval_examples = 0
        interval_windows = 0
        t_flush = time.perf_counter()

    done = False
    first_dispatch = True
    # The dispatch/drain region runs under OOM forensics: an XLA
    # RESOURCE_EXHAUSTED escaping it writes the fluxmpi_oom.<proc>.json
    # census bundle before re-raising (error path only — the happy path
    # pays a zero-cost try frame).
    try:
      while not done:
        if epochs is not None and epochs_done >= epochs:
            break
        if steps is not None and updates >= steps:
            break  # a resumed run may already have met the total budget
        # A resumed partial epoch starts its dispatch count at the
        # restored cursor so full-pass detection stays exact.
        offset = resume_offset
        resume_offset = 0
        dispatched_this_epoch = offset
        yielded_this_pass = 0
        exhausted = False
        if fused_w:
            # ---- one-program flush windows ----------------------------
            # The loader hands over the device-resident pieces (staged
            # dataset, this epoch's permutation, the resume start) and
            # the host then performs ONE dispatch per window: gathers,
            # the window's updates, and the metric reduction all run
            # inside the compiled program. The host wait for the epoch
            # bring-up (permutation transfer) is the fused analogue of
            # the loader stall.
            with _tracing.span("loop.device_epoch", epoch=epochs_done):
                if gp_on:
                    clock = gp._clock
                    t0 = clock()
                    staged, perm, pos = batches.device_epoch()
                    gp.add("data_stall", clock() - t0)
                else:
                    staged, perm, pos = batches.device_epoch()
            nb = per_epoch
            # The cache-key fingerprint is invariant within a pass (the
            # program returns same-aval state by construction; staged
            # and perm are fixed per epoch): compute it ONCE here, not
            # per window — per-dispatch tree walks are exactly the host
            # work this path exists to remove.
            avals = (_aval_key(state), _aval_key(staged), _aval_key(perm))
            if pos % fused_w:
                # Mid-window resume: the short realignment window
                # dispatches (and flushes) first, which would mark the
                # run steady BEFORE the full-width program compiles —
                # and a legitimate warmup compile must never read as a
                # steady_state_retrace (or burn the auto-profiler's
                # once-per-run capture). Pre-build the full program now,
                # during warmup, when the budget says one will run.
                short = fused_w - pos % fused_w
                full_window_later = pos + short < nb or (
                    epochs is None or epochs_done + 1 < epochs
                )
                if full_window_later and (
                    steps is None or steps - updates > short
                ):
                    _window_program(fused_w, state, staged, perm, avals)
            while pos < nb:
                # A resume cursor landing inside a window (a pipelined
                # run's checkpoint, an elastic remap) realigns with ONE
                # shorter first window — sample-exact, and the flush
                # grid matches the uninterrupted run's from then on.
                width = fused_w - pos % fused_w if pos % fused_w else fused_w
                program = _window_program(width, state, staged, perm, avals)
                start_idx = np.int32(pos * lbs_fused)
                with _tracing.span(
                    "loop.dispatch", update=updates, width=width
                ):
                    if gp_on:
                        # The dispatch is the whole window's productive
                        # compute; the flush inside _post_dispatch drains
                        # it under its own step segment.
                        with gp.segment("step"):
                            state, out = program(
                                state, staged, perm, start_idx
                            )
                        gp.note_updates(width)
                    else:
                        state, out = program(state, staged, perm, start_idx)
                first_dispatch = False
                last_out = out
                last_width = width
                dispatches += 1
                # Watchdog liveness: the fused path never iterates the
                # loader, so the loader's per-fetch tick is gone — tick
                # per window dispatch instead (one int increment, kept
                # even with telemetry off, same as the loader's). The
                # host still blocks a whole window inside the flush
                # drain: size the watchdog deadline above one window's
                # wall time (see the fuse= docstring).
                notify_progress()
                batches.note_consumed(width)
                pos += width
                updates += width
                examples += width * gbs_fused
                interval_updates += width
                interval_examples += width * gbs_fused
                interval_windows += 1
                yielded_this_pass += 1
                # Every window boundary is a flush boundary: metrics,
                # anomaly rules, checkpoint saves, and preemption all
                # quantize to windows in fused mode.
                _post_dispatch(True)
                if done:
                    break
            if pos >= nb:
                epochs_done += 1
            continue
        source = _epoch_iter(batches, k)
        if gp_on:
            # Loader waits land in the data_stall bucket; the off path
            # iterates the source directly (no wrapper, no clock reads).
            source = _stall_timed(iter(source), gp)
        # An explicit next() (not a for loop) so the wait for the loader
        # is a span of its own; with tracing off, span() is one call.
        source = iter(source)
        while True:
            with _tracing.span("loop.fetch", update=updates):
                batch = next(source, _SOURCE_DRY)
            if batch is _SOURCE_DRY:
                exhausted = True
                break
            if gp_on:
                if first_dispatch and gp._flops_per_update is None:
                    # FLOPs per update from XLA's cost model, BEFORE the
                    # donating dispatch consumes the state buffers. The
                    # lowering this pays is compile work: attributed as
                    # such.
                    from ..utils.flops import cost_analysis_flops

                    with gp.segment("compile"):
                        flops = cost_analysis_flops(hot, state, batch)
                    if flops:
                        gp.set_flops_per_update(flops / k)
                # The first dispatch traces + compiles synchronously —
                # the compile bucket; steady-state dispatches (and the
                # window-full block on the oldest result) are the
                # productive step bucket.
                with gp.segment("compile" if first_dispatch else "step"):
                    with _tracing.span(
                        "loop.dispatch", update=updates, width=k
                    ):
                        state, out = hot(state, batch)
                    window.append(out)
                    if len(window) > in_flight:
                        with _tracing.span(
                            "loop.backpressure", update=updates
                        ):
                            jax.block_until_ready(window.popleft())
                gp.note_updates(k)
            else:
                with _tracing.span("loop.dispatch", update=updates, width=k):
                    state, out = hot(state, batch)
                window.append(out)
                if len(window) > in_flight:
                    with _tracing.span("loop.backpressure", update=updates):
                        jax.block_until_ready(window.popleft())
            first_dispatch = False
            last_out = out
            dispatches += 1
            n = _batch_examples(batch, k)
            updates += k
            examples += n
            interval_updates += k
            interval_examples += n
            dispatched_this_epoch += 1
            yielded_this_pass += 1
            _post_dispatch(interval_updates >= flush_every)
            if done:
                break
        if exhausted or dispatched_this_epoch == per_epoch:
            # Iterator ran dry, or the steps budget landed exactly on the
            # last dispatch of a sized source — either way a full pass.
            epochs_done += 1
        if not done and yielded_this_pass == 0 and offset == 0:
            # offset > 0 with nothing yielded is a resumed epoch whose
            # remainder was all consumed (e.g. only a ragged scan group
            # was left) — not a dry source; the next pass starts fresh.
            if epochs is not None and epochs_done >= epochs:
                break
            raise ValueError(
                "batch source ran dry before the requested budget "
                f"(updates={updates}, steps={steps}, epochs={epochs}); "
                "pass a re-iterable loader for multi-epoch runs"
            )

      if gp_on and window:
        # Draining after a preemption is badput the preemption caused;
        # a normal end-of-run drain is the tail of productive compute.
        with gp.segment("preemption_drain" if preempted else "step"):
            while window:
                jax.block_until_ready(window.popleft())
      else:
        while window:
            jax.block_until_ready(window.popleft())
      flush()
    except Exception as exc:
        _maybe_oom_forensics(
            exc, _live_registry() if record_metrics else None
        )
        raise
    if resize_to is not None:
        # The drain ended at the block_until_ready/flush above — close
        # the drain phase before any save work muddies it.
        rz.note_drained()
    if preempted:
        # Drained and flushed: bank the final boundary and exit cleanly.
        # The trace instant is the preemption event the schema validates.
        _tracing.instant("train.preemption", step=int(updates))
        if (
            checkpoint is not None
            and updates > last_saved
            and halt_rule is None
            and resize_to is None
        ):
            # Past the epoch-accounting block: a completed pass is
            # already in epochs_done. A halt-policy anomaly (set at the
            # stopping flush, or by the final post-drain flush above)
            # gates the emergency save like the periodic ones — a
            # preemption coinciding with a NaN must not make the
            # diverged state the newest restorable checkpoint. A live
            # resize defers to its own timed save below (a SIGTERM with
            # a resize target armed is a resize, not a plain
            # preemption).
            _save_ckpt(pass_counted=True)
    if resize_to is not None:
        # The resize's final save — timed end to end (including the
        # wait for any in-flight async writer) as the record's ``save``
        # phase, then the handoff stamp banks this world's half next to
        # the checkpoint for the resumed world to stitch.
        t_save = time.perf_counter()
        if updates > last_saved and halt_rule is None:
            _save_ckpt(pass_counted=True)
        checkpoint.wait_until_finished()
        rz.note_phase("save", time.perf_counter() - t_save)
        rz.write_handoff(
            getattr(checkpoint, "directory", "."),
            step=last_saved,
            from_processes=jax.process_count(),
            to_processes=resize_to,
        )
    if checkpoint is not None:
        checkpoint.wait_until_finished()
    seconds = time.perf_counter() - t_start
    loss = None
    if last_out is not None:
        if fused_w:
            loss = float(np.asarray(jax.device_get(last_out["loss"])))
        else:
            leaves = jax.tree_util.tree_leaves(last_out)
            if leaves:
                loss = float(np.asarray(jax.device_get(leaves[0])).mean())
    summary = {
        "updates": updates,
        "epochs": epochs_done,
        "examples": examples,
        "seconds": seconds,
        "updates_per_sec": updates / seconds if seconds > 0 else 0.0,
        "examples_per_sec": examples / seconds if seconds > 0 else 0.0,
        "loss": loss,
        "preempted": preempted,
        "resized_to": resize_to,
        "resumed_from": resumed_from,
        "anomaly": halt_rule,
        # Host dispatches of the compiled hot/window program — the
        # number the fused path exists to shrink (1 per window vs 1 per
        # batch); dispatches/updates is the benchmark's
        # ``dispatches_per_update``.
        "dispatches": dispatches,
        "fused_window": fused_w or None,
    }
    if fused_w:
        summary["window_compile_seconds"] = window_compile["seconds"]
        summary["window_cache"] = {
            "hits": window_compile["hits"],
            "misses": window_compile["misses"],
        }
    if gp_on:
        # Final record covers the drain/emergency-save tail the last
        # in-loop flush could not see; the report rides the summary so
        # callers get the breakdown without touching the registry.
        gp.record(_live_registry() if record_metrics else None)
        summary["goodput"] = gp.report()
    if exp_on:
        # Terminal status: /status keeps answering after the loop exits
        # (an operator asking "why did it stop" gets the outcome, not a
        # stale "running").
        exporter.note_status(
            phase=(
                "resizing"
                if resize_to is not None
                else (
                    "preempted"
                    if preempted
                    else ("halted" if halt_rule else "finished")
                )
            ),
            updates=updates,
            examples=examples,
            epochs=epochs_done,
            loss=loss,
            preempted=preempted,
            anomaly=halt_rule,
            dispatches=dispatches,
        )
    return state, summary
