"""Compiled data-parallel train-step factories.

This is where the reference's training-loop integration
(reference: README.md:31-70 — Zygote pullback, per-leaf allreduce via
``DistributedOptimizer``/``allreduce_gradients``, ``Optimisers.update``)
becomes ONE compiled XLA program per step: forward, backward, gradient
all-reduce over ICI, and optimizer update fused and scheduled together, with
buffer donation so parameters update in place in HBM.

Two styles, same math:

- ``style="auto"`` (default, fastest): the step is jitted with explicit
  shardings — state replicated, batch laid out over the data-parallel axis —
  and XLA's SPMD partitioner inserts and overlaps the gradient reduction.
  The loss function sees the *global* batch.
- ``style="shard_map"`` (explicit, reference-shaped): the step body runs
  per-device on the local batch shard and calls the collective explicitly
  (``psum``/``pmean`` — the compiled analogue of the reference's
  ``allreduce_gradients``, src/optimizer.jl:45-65). Use this when you want
  manual control, e.g. collectives inside custom VJPs.

Gradient semantics default to ``grad_reduce="mean"`` (the mathematically
data-parallel-correct average). The reference's sum-then-user-scales
convention (src/optimizer.jl:11-14) is available as ``grad_reduce="sum"``;
pass ``grad_reduce=None`` if your optimizer already reduces (e.g. a
``DistributedOptimizer(axis_name=...)``) so gradients aren't reduced twice.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import config
from ..runtime import global_mesh
from ._compat import shard_map_unchecked

__all__ = [
    "TrainState",
    "make_train_step",
    "make_eval_step",
    "make_window_program",
    "replicate",
    "shard_batch",
]


class TrainState(flax.struct.PyTreeNode):
    """Replicated training state: parameters, optimizer state, and mutable
    model state (e.g. BatchNorm batch_stats). A pure pytree — safe to
    donate, checkpoint, and synchronize."""

    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any = None

    @classmethod
    def create(
        cls,
        params: Any,
        optimizer: optax.GradientTransformation,
        model_state: Any = None,
    ) -> "TrainState":
        return cls(
            step=jnp.zeros((), dtype=jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            model_state=model_state,
        )


def replicate(tree: Any, mesh: Mesh | None = None) -> Any:
    """Lay a pytree out replicated over the mesh (every device holds the
    full value) — the device-level completion of :func:`synchronize`."""
    mesh = mesh or global_mesh()
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.asarray(x), sharding), tree
    )


def shard_batch(
    batch: Any,
    mesh: Mesh | None = None,
    axis_name: str | None = None,
    *,
    spec: P | None = None,
) -> Any:
    """Lay a host batch out over the mesh — by default the leading (batch)
    dimension over the data-parallel axis; pass ``spec`` for richer layouts
    (e.g. ``P("dp", "sp")`` to also shard the sequence dimension)."""
    mesh = mesh or global_mesh()
    if spec is not None and axis_name is not None:
        raise ValueError("pass either axis_name or spec, not both")
    if spec is None:
        spec = P(axis_name or config.DP_AXIS_NAME)
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


_DEFAULT_REGISTRY = object()  # sentinel: re-read get_registry() every step


def _tag_scan_steps(step: Any, scan_steps: int) -> None:
    """Record the step's scan width as an attribute so the pipelined
    driver (:func:`fluxmpi_tpu.parallel.train_loop`) can pick it up
    without the caller restating it."""
    step.scan_steps = scan_steps


def _bank_aux_meta(
    compiled: Any,
    aux_names: tuple[str, ...],
    stats_depth: int | None,
    workers: int,
) -> None:
    """Record the compiled step's auxiliary-output structure (and, with
    model stats baked in, the plane metadata) so ``train_loop`` can
    unpack the flush values without guessing."""
    compiled.__fluxmpi_aux__ = aux_names
    if stats_depth is not None:
        compiled.__fluxmpi_model_stats_meta__ = {
            "depth": stats_depth,
            "workers": workers,
        }


def _resolve_metrics(metrics: Any) -> tuple[Any, Any, Any]:
    """Normalize a ``metrics=`` spec to (registry, monitor, hook)."""
    from ..telemetry import MetricsRegistry, TrainingMonitor

    if metrics is True:
        return _DEFAULT_REGISTRY, None, None
    if isinstance(metrics, TrainingMonitor):
        return metrics.registry, metrics, None
    if isinstance(metrics, MetricsRegistry):
        return metrics, None, None
    if callable(metrics):
        return None, None, metrics
    raise ValueError(
        "metrics must be True, a MetricsRegistry, a TrainingMonitor, or a "
        f"callable hook; got {metrics!r}"
    )


def _last_scan_entry(tree: Any) -> Any:
    """Last scanned element of each leaf of a stacked ``[K]`` host tree
    (the flush-boundary selection: stats describe the newest update)."""
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[-1], tree)


def _instrument_step(
    compiled,
    metrics: Any,
    scan_steps: int,
    *,
    stats_on: bool = False,
    stats_workers: int = 1,
):
    """Wrap a compiled step that returns ``(state, (loss, grad_norm[,
    model_stats]))`` into the public ``(state, loss)`` signature,
    recording telemetry.

    Timing follows the :func:`~fluxmpi_tpu.utils.step_timer` discipline:
    the clock stops only after blocking on the step's outputs, so async
    dispatch cannot under-report. Everything else is a handful of host
    float/dict ops — cheap enough to leave on (emission cost is the
    sink's business, at flush time).

    The step is also a trace span (``train.step`` on the
    :mod:`~fluxmpi_tpu.telemetry.tracing` timeline when tracing is
    enabled; one no-op call otherwise) and a watchdog progress tick —
    an armed :class:`~fluxmpi_tpu.telemetry.Watchdog` counts completed
    steps as liveness.

    With ``stats_on`` (the model-internals plane baked stats into the
    program) the per-layer tree is transferred and emitted per call —
    direct step users get per-step granularity; ``train_loop`` bypasses
    this wrapper and consumes the same tree at flush granularity. A
    ``metrics`` of ``None``/``False`` records nothing but still strips
    the auxiliary outputs (the stats-only wrapper).
    """
    from ..telemetry import get_registry
    from ..telemetry import modelstats as _modelstats
    from ..telemetry import tracing as _tracing
    from ..telemetry.watchdog import notify_progress
    from ..utils.profiling import step_timer

    record_metrics = metrics is not None and metrics is not False
    reg, monitor, hook = (None, None, None)
    if record_metrics:
        reg, monitor, hook = _resolve_metrics(metrics)

    def step(state, batch):
        holder: dict[str, float] = {}
        with _tracing.span("train.step"):
            with step_timer(holder) as t:
                new_state, aux = compiled(state, batch)
                loss, gnorm = aux[0], aux[1]
                t.watch((loss, gnorm))
        notify_progress()
        seconds = holder["seconds"]
        leaves = jax.tree_util.tree_leaves(batch)
        examples = 0
        if leaves and getattr(leaves[0], "ndim", 0):
            examples = int(np.shape(leaves[0])[0])
            if scan_steps > 1:  # leading axis is scan time, not data
                examples *= int(np.shape(leaves[0])[1])
        if record_metrics:
            loss_h = np.asarray(jax.device_get(loss))
            gnorm_h = np.asarray(jax.device_get(gnorm))
            record = {
                "step_seconds": seconds,
                "loss": float(loss_h.mean()),
                "grad_norm": float(gnorm_h.mean()),
                "examples": examples,
                "examples_per_sec": examples / seconds if seconds > 0 else 0.0,
                "steps": scan_steps,
            }
            registry = get_registry() if reg is _DEFAULT_REGISTRY else reg
            if registry is not None:
                registry.histogram("train.step_seconds").observe(seconds)
                registry.gauge("train.loss").set(record["loss"])
                registry.gauge("train.grad_norm").set(record["grad_norm"])
                registry.gauge("train.examples_per_sec").set(
                    record["examples_per_sec"]
                )
                registry.counter("train.steps").inc(scan_steps)
                registry.counter("train.examples").inc(examples)
            if monitor is not None:
                monitor.observe_step(seconds)
            if hook is not None:
                hook(record)
        if stats_on:
            ms = _modelstats.get_model_stats()
            if ms is not None and ms.enabled:
                stats_host = jax.device_get(aux[2])
                if scan_steps > 1:
                    stats_host = _last_scan_entry(stats_host)
                ms.observe_flush(
                    stats_host,
                    registry=(
                        get_registry() if reg is _DEFAULT_REGISTRY else reg
                    ),
                    batch_examples=(
                        examples / scan_steps if scan_steps > 0 else None
                    ),
                    workers=stats_workers,
                )
        return new_state, loss

    step.__wrapped__ = compiled  # cost_analysis / AOT access to the jit
    # Distinct from __wrapped__, which jax.jit ALSO sets (to the raw Python
    # function) — the loop driver must only unwrap instrumented steps.
    step.__fluxmpi_compiled__ = compiled
    # The spec rides along so train_loop can honor it at flush boundaries
    # after unwrapping the per-step instrumentation.
    step.__fluxmpi_metrics__ = metrics
    step.scan_steps = scan_steps  # loop-driver metadata (see parallel.loop)
    return step


def _plan_defaults(
    parallel: Any,
    mesh: Mesh | None,
    axis_name: str | None,
    batch_spec: Any | None,
    state_sharding: Any | None,
    caller: str,
) -> tuple[Any, Mesh, str, Any, Any]:
    """Derive a step factory's layout defaults from a ``parallel=``
    argument (explicit arguments win): the plan's mesh, dp axis name,
    batch spec, and BANKED state sharding. A parameter-sharding plan
    with nothing banked raises — the step must pin the same layout the
    state was placed with."""
    from .plan import resolve_parallel

    plan = resolve_parallel(parallel)
    mesh = mesh or plan.mesh
    if axis_name is None:
        axis_name = plan.dp_axis_name
    if batch_spec is None:
        batch_spec = plan.batch_spec
    if state_sharding is None:
        state_sharding = plan.state_sharding
        if state_sharding is None and plan.shards_parameters:
            raise ValueError(
                f"this ParallelConfig shards parameters (fsdp/tp axes or "
                f"a rules table) but no layout is banked — call "
                f"plan.shard_state(state) before {caller}(parallel=plan) "
                f"so the compiled program pins the same layout the state "
                f"was placed with"
            )
    return plan, mesh, axis_name, batch_spec, state_sharding


def _installed_plan_defaults(
    mesh: Mesh | None, axis_name: str | None, batch_spec: Any | None
) -> tuple[Any, Mesh | None, str | None, Any | None]:
    """Mirror the loader's default for legacy (no ``parallel=``) step
    calls: when ``init(parallel=)`` installed a plan and the step rides
    a mesh carrying its data axes, derive the batch layout / data-axis
    defaults from the plan so the step and the loader agree on who
    consumes which batch shard (under a composed dp×fsdp plan the
    loader shards the batch over BOTH axes). An explicit ``axis_name``
    or ``batch_spec`` opts the whole call out — the caller chose their
    own batch layout, so the plan must not supply the OTHER half (or
    the dp-worker accounting that goes with its wider layout). Callers
    manage their own state layout (no banked-sharding pull, unlike
    ``parallel=``)."""
    from ..runtime import global_plan

    if axis_name is not None or batch_spec is not None:
        return None, mesh, axis_name, batch_spec
    plan = global_plan()
    if plan is None or not plan.covers(mesh):
        return None, mesh, axis_name, batch_spec
    return plan, mesh, plan.dp_axis_name, plan.batch_spec


def _kernel_layout(mesh: Mesh, batch_spec: P, plan: Any):
    """The context a ``style="auto"`` factory traces user code in: XLA
    partitions the program but cannot partition a Pallas kernel, so the
    kernels are told the layout — batch over the batch spec's leading
    entry, heads over the plan's tp axis — and run per device (see
    :func:`fluxmpi_tpu.ops.flash_attention.spmd_attention_layout`)."""
    from ..ops.flash_attention import spmd_attention_layout

    return spmd_attention_layout(
        mesh,
        batch_spec[0] if len(batch_spec) else None,
        plan.axis_name("tp") if plan is not None else None,
    )


def make_train_step(
    loss_fn: Callable[[Any, Any, Any], tuple[jax.Array, Any]],
    optimizer: optax.GradientTransformation,
    *,
    parallel: Any | None = None,
    mesh: Mesh | None = None,
    axis_name: str | None = None,
    style: str = "auto",
    grad_reduce: str | None = "mean",
    state_reduce: str = "mean",
    donate: bool | None = None,
    state_sharding: Any | None = None,
    batch_spec: P | None = None,
    remat: bool = False,
    grad_accum_steps: int = 1,
    scan_steps: int = 1,
    policy: Any | None = None,
    metrics: Any | None = None,
    model_stats: Any | None = None,
) -> Callable[[TrainState, Any], tuple[TrainState, jax.Array]]:
    """Build a compiled data-parallel train step.

    Args:
      loss_fn: ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``.
        Stateless models return ``None`` as the new state. Under
        ``style="auto"`` it sees the global batch; under ``style="shard_map"``
        the per-device shard.
      optimizer: any optax transformation (plain — see ``grad_reduce=None``
        for pre-reducing optimizers like ``DistributedOptimizer``).
      parallel: a :class:`~fluxmpi_tpu.parallel.ParallelConfig` or
        resolved plan — the step derives ``mesh``, ``axis_name``,
        ``batch_spec``, and ``state_sharding`` from the ONE plan instead
        of per-call arguments (explicit arguments still win). A plan
        that shards parameters (fsdp/tp axes or a rules table) requires
        :meth:`~fluxmpi_tpu.parallel.plan.ResolvedPlan.shard_state` to
        have been called first — the banked layout is what the compiled
        step pins; a dp(/sp)-only plan needs nothing banked.
        ``style="auto"`` only. The string ``"auto"`` resolves to the
        plan the layout autotuner installed under
        ``init(parallel="auto")`` (raises, naming
        :func:`fluxmpi_tpu.parallel.autotune.autotune`, when none is
        installed yet).
      mesh: defaults to the plan's mesh, else the runtime's global mesh.
      axis_name: data-parallel axis (default from the plan, else config).
      style: ``"auto"`` (XLA SPMD partitioner inserts collectives) or
        ``"shard_map"`` (explicit per-device body + psum/pmean).
      grad_reduce: ``"mean"`` | ``"sum"`` | ``None`` (no reduction here).
        Only meaningful for ``style="shard_map"``; under ``"auto"`` the
        partitioner derives the reduction from the shardings.
      state_reduce: how to combine per-device mutable model state under
        ``shard_map`` (``"mean"`` for BatchNorm-style running stats, or
        ``"local"`` to keep replica-local values — the reference never
        reduces state during training, syncing only at init,
        SURVEY.md §7 hard parts).
      donate: donate the TrainState buffers (in-place update in HBM).
        Defaults to the ``donate_buffers`` preference.
      state_sharding: optional pytree of :class:`NamedSharding` matching the
        :class:`TrainState` (see :func:`fluxmpi_tpu.parallel.sharding.shard_tree`)
        — enables tensor-parallel / FSDP parameter+optimizer layouts instead
        of full replication. ``style="auto"`` only.
      batch_spec: PartitionSpec for every batch leaf (default
        ``P(axis_name)`` — batch dim over the data-parallel axis). Use e.g.
        ``P("dp", "sp")`` to also shard the sequence dimension.
        ``style="auto"`` only.
      remat: rematerialize the forward pass during the backward
        (``jax.checkpoint`` on the loss) — trades FLOPs for HBM so larger
        per-chip batches / longer sequences fit. ``True`` saves nothing
        (recompute everything); the string ``"dots"`` applies the
        ``checkpoint_dots`` policy instead — matmul outputs are saved,
        only the cheap elementwise work recomputes (usually the better
        trade on TPU, where the MXU is the scarce resource).
      grad_accum_steps: split each batch into this many microbatches and
        accumulate (mean) gradients over a ``lax.scan`` before the single
        optimizer update — large effective batches without the HBM. The
        leading batch dim of every batch leaf must be divisible by it.
        ``style="auto"`` only.
      scan_steps: compile this many SEQUENTIAL optimizer updates into one
        dispatch (an outer ``lax.scan``): every batch leaf carries an
        extra leading ``scan_steps`` axis, and the step returns the
        ``[scan_steps]`` per-update losses. One host→device dispatch then
        drives K updates — amortizing per-step dispatch latency, which on
        very fast chips can otherwise dominate small step times (no
        analogue in the reference: its per-step NCCL launches are
        host-driven by construction). Composes with
        ``grad_accum_steps`` (accumulation nests inside each scanned
        update). ``style="auto"`` only.
      policy: optional :class:`fluxmpi_tpu.utils.Policy` — the params are
        cast to its ``compute_dtype`` ENTERING ``loss_fn`` while the
        :class:`TrainState` keeps full-precision masters (the cast's vjp
        returns the gradient cotangent to the master dtype, so the
        optimizer update runs in f32). Batch leaves are left alone —
        cast inputs inside ``loss_fn`` where you know which leaves are
        images vs integer ids (``policy.cast_to_compute`` touches only
        float leaves, so passing the whole batch through it is usually
        right).
      metrics: optional telemetry hook (``None``/``False`` = off).
        ``True`` records into the default
        :func:`fluxmpi_tpu.telemetry.get_registry`; a
        :class:`~fluxmpi_tpu.telemetry.MetricsRegistry` records into it; a
        :class:`~fluxmpi_tpu.telemetry.TrainingMonitor` records into the
        monitor's registry AND feeds its periodic collect (device memory,
        cross-host straggler aggregation); a callable receives a dict per
        step. Recorded per step: ``train.step_seconds`` (histogram, timed
        by the :func:`~fluxmpi_tpu.utils.step_timer` discipline — the
        clock stops only after blocking on the step's outputs),
        ``train.loss``, ``train.grad_norm`` (global norm of the gradients
        the optimizer consumed; the local shard's under
        ``style="shard_map"`` with ``grad_reduce=None``),
        ``train.examples_per_sec``, and cumulative ``train.steps`` /
        ``train.examples``. The per-step block on the loss serializes
        async dispatch — prefer a larger effective step (``scan_steps``)
        when enabling this on short steps.
      model_stats: fold the model-internals plane's per-layer stats tree
        into the compiled program (``None``, the default, follows the
        installed :class:`~fluxmpi_tpu.telemetry.ModelStats` plane —
        ``init(model_stats=True)`` / ``FLUXMPI_TPU_MODEL_STATS=1``;
        ``True``/``False`` force it, an int sets the grouping depth):
        per-layer gradient/parameter/update norms and nonfinite-gradient
        counts (NaN provenance), grouped by leaf-path depth so the tree
        stays O(layers), plus — under ``style="shard_map"`` with a
        ``grad_reduce`` — the pre-allreduce local gradient sq-norm the
        gradient-noise-scale estimate (B_simple) needs. Computed from
        the values the program already materializes; the update math is
        untouched (a run with it on is bit-identical to one with it
        off). Consumed at ``train_loop`` flush boundaries (one tiny
        device→host copy per flush) or per call when the step is driven
        directly; see :mod:`fluxmpi_tpu.telemetry.modelstats` and
        docs/observability.md "Model internals".

    Returns:
      ``step(state, batch) -> (new_state, loss)`` — compiled, collective
      communication included; call it in a plain Python loop. With
      ``metrics=`` the same signature, instrumented.
    """
    plan = None
    if isinstance(parallel, str):
        # parallel="auto": consume the layout the autotuner installed as
        # the global plan (the init(parallel="auto") contract).
        if parallel != "auto":
            raise ValueError(
                f'parallel= accepts a ParallelConfig, a ResolvedPlan, or '
                f'the string "auto", got {parallel!r}'
            )
        from ..runtime import global_plan as _global_plan

        parallel = _global_plan()
        if parallel is None:
            raise ValueError(
                'make_train_step(parallel="auto") found no installed '
                "plan — run the layout search first: "
                "fluxmpi_tpu.parallel.autotune.autotune(loss_fn, "
                "optimizer, params, sample_batch) under "
                'init(parallel="auto") installs its winner as the '
                "global plan (a banked winner is reused without trials)"
            )
    if parallel is not None:
        if style != "auto":
            raise ValueError(
                "parallel= requires style='auto' (the plan's layouts are "
                "partitioner-driven; shard_map takes explicit axis_name=)"
            )
        plan, mesh, axis_name, batch_spec, state_sharding = _plan_defaults(
            parallel, mesh, axis_name, batch_spec, state_sharding,
            "make_train_step",
        )
    elif style == "auto":
        plan, mesh, axis_name, batch_spec = _installed_plan_defaults(
            mesh, axis_name, batch_spec
        )
    mesh = mesh or global_mesh()
    name = axis_name or config.DP_AXIS_NAME
    if donate is None:
        donate = bool(config.load_preference("donate_buffers"))
    if style not in ("auto", "shard_map"):
        raise ValueError("style must be 'auto' or 'shard_map'")
    if grad_reduce not in ("mean", "sum", None):
        raise ValueError("grad_reduce must be 'mean', 'sum', or None")

    if policy is not None:
        inner_loss = loss_fn

        def loss_fn(p, mstate, batch):  # noqa: F811 - deliberate rewrap
            return inner_loss(policy.cast_to_compute(p), mstate, batch)

    single_spec = P(name) if batch_spec is None else batch_spec
    if style == "auto":
        spmd_loss = loss_fn

        def loss_fn(p, mstate, batch):  # noqa: F811 - deliberate rewrap
            with _kernel_layout(mesh, single_spec, plan):
                return spmd_loss(p, mstate, batch)

    if remat:
        if remat == "dots":
            loss_fn = jax.checkpoint(
                loss_fn,
                policy=jax.checkpoint_policies.checkpoint_dots,
            )
        elif remat is True:
            loss_fn = jax.checkpoint(loss_fn)
        else:
            raise ValueError(
                f"remat must be False, True, or 'dots', got {remat!r}"
            )
    grad_and_aux = jax.value_and_grad(loss_fn, has_aux=True)

    def _apply_update(ts: TrainState, grads, loss, new_mstate):
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(
                grads, ts.opt_state, ts.params
            )
            params = optax.apply_updates(ts.params, updates)
        return (
            TrainState(
                step=ts.step + 1,
                params=params,
                opt_state=opt_state,
                model_state=new_mstate,
            ),
            loss,
            updates,
        )

    if grad_accum_steps < 1:
        raise ValueError("grad_accum_steps must be >= 1")
    if grad_accum_steps > 1 and style != "auto":
        raise ValueError("grad_accum_steps requires style='auto'")
    if scan_steps < 1:
        raise ValueError("scan_steps must be >= 1")
    if scan_steps > 1 and style != "auto":
        raise ValueError("scan_steps requires style='auto'")

    # False is off, same as None — `metrics=args.telemetry` with a bool
    # flag must not blow up at build time.
    instrument = metrics is not None and metrics is not False
    if instrument:
        _resolve_metrics(metrics)  # reject bad specs at build, not step 1

    # Model-internals plane: resolved at BUILD time (the stats tree is
    # part of the compiled program — a plane installed later cannot
    # reach into an existing executable). None when off: the program
    # then computes nothing extra (the zero-cost contract).
    from ..telemetry import modelstats as _modelstats

    stats_depth = _modelstats.resolve_step_spec(model_stats)
    stats_on = stats_depth is not None
    if plan is not None:
        # The plan's data axes (dp × fsdp) all consume distinct batch
        # shards — that product, not one axis, is the worker count the
        # noise-scale / examples accounting needs. Sized from the mesh
        # the step actually compiles against (an explicit mesh= override
        # may carry the axes at different sizes than the plan's own).
        dp_workers = int(
            np.prod(
                [mesh.shape[a] for a in plan.data_axes if a in mesh.shape]
            )
        )
    else:
        dp_workers = int(mesh.shape[name]) if name in mesh.shape else 1
    aux_names: tuple[str, ...] = ("loss",)
    if instrument or stats_on:
        aux_names = ("loss", "grad_norm")
    if stats_on:
        aux_names = aux_names + ("model_stats",)

    def _result(ts: TrainState, new_ts: TrainState, loss, grads, updates,
                noise=None):
        # Instrumented steps carry the global grad-norm out of the
        # compiled program alongside the loss (computing it host-side
        # would re-materialize the gradient tree); with model stats on,
        # the per-layer tree rides the same slot. The wrapper strips
        # the extras so the public signature stays (state, loss).
        if not instrument and not stats_on:
            return new_ts, loss
        aux = [loss, optax.global_norm(grads)]
        if stats_on:
            stats = _modelstats.compute_stats(
                grads, ts.params, updates, depth=stats_depth
            )
            if noise is not None:
                stats["noise"] = noise
            aux.append(stats)
        return new_ts, tuple(aux)

    if style == "auto":

        # With an FSDP/TP state layout, pin the gradients to the parameter
        # shardings right at the grad/update boundary: the partitioner then
        # owns a sharded-output reduction (reduce-scatter on TPU) instead of
        # being free to keep full gradients replicated.
        param_shardings = getattr(state_sharding, "params", None)

        def _pin_grads(grads):
            if param_shardings is None:
                return grads
            return jax.lax.with_sharding_constraint(grads, param_shardings)

        if grad_accum_steps == 1:

            def step(ts: TrainState, batch):
                (loss, new_mstate), grads = grad_and_aux(
                    ts.params, ts.model_state, batch
                )
                grads = _pin_grads(grads)
                new_ts, loss, upd = _apply_update(ts, grads, loss, new_mstate)
                return _result(ts, new_ts, loss, grads, upd)

        else:

            def step(ts: TrainState, batch):
                k = grad_accum_steps

                def to_micro(x):
                    if x.shape[0] % k:
                        raise ValueError(
                            f"batch dim {x.shape[0]} not divisible by "
                            f"grad_accum_steps {k}"
                        )
                    return x.reshape(k, x.shape[0] // k, *x.shape[1:])

                micro = jax.tree_util.tree_map(to_micro, batch)
                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros_like(p), ts.params
                )

                def body(carry, mb):
                    acc_g, acc_l, mstate = carry
                    (loss, new_ms), g = grad_and_aux(ts.params, mstate, mb)
                    acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                    return (acc_g, acc_l + loss, new_ms), None

                (g, l, ms), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros(()), ts.model_state), micro
                )
                grads = _pin_grads(
                    jax.tree_util.tree_map(lambda x: x / k, g)
                )
                new_ts, loss, upd = _apply_update(ts, grads, l / k, ms)
                return _result(ts, new_ts, loss, grads, upd)

        single_update = step  # the one-update body the fused window scans
        if scan_steps > 1:
            single = step

            def step(ts: TrainState, batches):
                return jax.lax.scan(single, ts, batches)

        replicated = NamedSharding(mesh, P())
        state_in = replicated if state_sharding is None else state_sharding
        spec = single_spec
        if scan_steps > 1:
            # Leading scan axis is time, not data: unsharded.
            spec = P(None, *spec)
        batch_sharding = NamedSharding(mesh, spec)
        # `replicated` is a pytree PREFIX over the second output slot, so
        # it covers both the bare loss and the instrumented (loss, gnorm).
        compiled = jax.jit(
            step,
            in_shardings=(state_in, batch_sharding),
            out_shardings=(state_in, replicated),
            donate_argnums=(0,) if donate else (),
        )
        _tag_scan_steps(compiled, scan_steps)
        # Everything make_window_program needs to re-fuse this step's math
        # into a one-program flush window (batch gather + K updates +
        # metric reduction in a single lax.scan). The SINGLE-update body
        # rides along — the window does its own scan, so a scan_steps
        # wrapper here is irrelevant to the fused path.
        compiled.__fluxmpi_window_meta__ = {
            "single": single_update,
            "state_in": state_in,
            "batch_spec": single_spec,
            "mesh": mesh,
            "donate": donate,
            "instrument": instrument,
            "aux": aux_names,
            "stats_depth": stats_depth,
        }
        _bank_aux_meta(compiled, aux_names, stats_depth, dp_workers)
        if instrument or stats_on:
            return _instrument_step(
                compiled,
                metrics if instrument else False,
                scan_steps,
                stats_on=stats_on,
                stats_workers=dp_workers,
            )
        return compiled
    if state_sharding is not None or batch_spec is not None:
        raise ValueError(
            "state_sharding/batch_spec require style='auto' (shard_map style "
            "replicates state per the reference's layout)"
        )

    # style == "shard_map": explicit per-device body. NOTE: shard_map's
    # replication checker (check_vma) auto-inserts a psum on the cotangent
    # of replicated inputs, which would pre-reduce the gradients and make
    # the explicit collectives below double-count. Disable it so gradients
    # stay device-local until the explicit reduction — the reference's
    # "each rank holds local grads, then allreduce" model
    # (src/optimizer.jl:45-65).
    # The noise-scale ingredients exist exactly where the reference's
    # allreduce structure does: each rank's pre-allreduce gradient is an
    # independent estimate at the per-rank batch, and the reduced
    # gradient the estimate at the global batch — the two norms B_simple
    # needs (telemetry/modelstats.noise_scale). The partitioner-driven
    # style="auto" path never materializes a per-rank gradient, so this
    # is deliberately shard_map-only.
    noise_on = stats_on and grad_reduce in ("mean", "sum")

    def step_body(ts: TrainState, batch):
        (loss, new_mstate), grads = grad_and_aux(ts.params, ts.model_state, batch)
        local_sq = optax.global_norm(grads) ** 2 if noise_on else None
        if grad_reduce == "mean":
            grads = jax.lax.pmean(grads, name)
            loss = jax.lax.pmean(loss, name)
        elif grad_reduce == "sum":
            grads = jax.lax.psum(grads, name)
            loss = jax.lax.psum(loss, name)
        if new_mstate is not None and state_reduce == "mean":
            new_mstate = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, name)
                if jnp.issubdtype(jnp.asarray(s).dtype, jnp.inexact)
                else s,
                new_mstate,
            )
        noise = None
        if noise_on:
            global_sq = optax.global_norm(grads) ** 2
            if grad_reduce == "sum":
                # The summed gradient is workers × the mean; B_simple's
                # "big batch" estimator is the AVERAGE, so rescale its
                # sq-norm (the optimizer still consumes the sum).
                global_sq = global_sq / float(dp_workers) ** 2
            noise = {
                "local_sqnorm": jax.lax.pmean(local_sq, name),
                "global_sqnorm": global_sq,
            }
        new_ts, loss, upd = _apply_update(ts, grads, loss, new_mstate)
        return _result(ts, new_ts, loss, grads, upd, noise=noise)

    mapped = shard_map_unchecked(
        step_body, mesh, in_specs=(P(), P(name)), out_specs=(P(), P())
    )
    compiled = jax.jit(mapped, donate_argnums=(0,) if donate else ())
    _tag_scan_steps(compiled, 1)
    _bank_aux_meta(compiled, aux_names, stats_depth, dp_workers)
    if instrument or stats_on:
        return _instrument_step(
            compiled,
            metrics if instrument else False,
            1,
            stats_on=stats_on,
            stats_workers=dp_workers,
        )
    return compiled


def make_window_program(
    step: Any,
    *,
    width: int,
    lbs: int,
) -> Any:
    """Fuse a whole flush window into ONE jitted program: ``width``
    sequential optimizer updates, each batch gathered from the
    device-resident dataset inside the scan, with the interval metrics
    (last/sum/max loss, last grad-norm for instrumented steps) folded
    into the scan carry.

    The returned callable has signature ``(state, data, perm, start) ->
    (state, metrics)`` where ``data`` is the staged (replicated) dataset
    pytree and ``perm`` the epoch permutation from
    :meth:`fluxmpi_tpu.data.DistributedDataLoader.device_epoch`, and
    ``start`` is the first sample offset (``batch_cursor × lbs``, a
    traced scalar — windows at different positions share one
    executable). ``metrics`` is a dict of f32 scalars: ``loss`` (the
    last update's, the value the pipelined flush reports), ``loss_sum``
    / ``loss_max`` over the window, plus ``grad_norm`` when the step was
    built with ``metrics=``. The train state is donated (per the step's
    own ``donate`` setting) so the carry updates in place in HBM — the
    host performs one dispatch and one tiny device→host metrics transfer
    per window instead of ``width`` gather+step dispatch pairs.

    ``step`` must come from ``make_train_step(style="auto")`` — the
    factory banks the single-update body and sharding layout it needs
    (``__fluxmpi_window_meta__``); the batch gather is the same
    :func:`fluxmpi_tpu.data._gather_batch` math the per-batch
    device-gather path jits, so both paths consume identical batches.
    ``train_loop(fuse="window")`` builds, AOT-compiles
    (``.lower().compile()``), and caches these per width — see
    docs/performance.md, "One-program windows".
    """
    from ..data import _gather_batch

    meta = getattr(step, "__fluxmpi_window_meta__", None)
    if meta is None:
        raise ValueError(
            "make_window_program needs a step built by "
            "make_train_step(style='auto') — shard_map-style and foreign "
            "steps carry no fused-window metadata"
        )
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    single = meta["single"]
    mesh = meta["mesh"]
    instrument = meta["instrument"]
    # Aux structure of the single-update body: (loss[, grad_norm[,
    # model_stats]]) — steps built before the model-internals plane
    # banked "aux" fall back to the instrument flag's two shapes.
    aux_names = meta.get("aux") or (
        ("loss", "grad_norm") if instrument else ("loss",)
    )
    carries_aux = len(aux_names) > 1
    stats_on = "model_stats" in aux_names
    if stats_on:
        from ..telemetry import modelstats as _modelstats
    batch_sharding = NamedSharding(mesh, meta["batch_spec"])
    replicated = NamedSharding(mesh, P())

    def window(ts: TrainState, data, perm, start):
        def body(carry, i):
            st, m = carry
            with jax.named_scope("batch_gather"):
                batch = _gather_batch(data, perm, start + i * lbs, lbs)
                # Pin the gathered batch to the step's data-parallel
                # layout so the partitioner sees exactly what the
                # per-batch gather jit's out_shardings produced.
                batch = jax.lax.with_sharding_constraint(
                    batch, batch_sharding
                )
            out = single(st, batch)
            stats = None
            if carries_aux:
                new_st, aux = out
                loss, gnorm = aux[0], aux[1]
                if stats_on:
                    stats = aux[2]
            else:
                new_st, loss = out
                gnorm = None
            # f32 carry: exact for f32/bf16 losses, and float() of the
            # device_get'd value matches the pipelined flush bit for bit.
            loss32 = loss.astype(jnp.float32)
            new_m = {
                "loss": loss32,
                "loss_sum": m["loss_sum"] + loss32,
                "loss_max": jnp.maximum(m["loss_max"], loss32),
            }
            if carries_aux:
                new_m["grad_norm"] = gnorm.astype(jnp.float32)
            if stats is not None:
                # Last update's tree wins the carry — the same
                # flush-boundary selection the pipelined path makes
                # ([-1] of the stacked scan outputs). Already f32 by
                # construction (compute_stats accumulates in f32).
                new_m["model_stats"] = stats
            return (new_st, new_m), None

        m0 = {
            "loss": jnp.zeros((), jnp.float32),
            "loss_sum": jnp.zeros((), jnp.float32),
            "loss_max": jnp.full((), -jnp.inf, jnp.float32),
        }
        if carries_aux:
            m0["grad_norm"] = jnp.zeros((), jnp.float32)
        if stats_on:
            # Zeros with compute_stats' exact structure (both sides
            # derive groups from the same param treedef + depth).
            m0["model_stats"] = _modelstats.stats_zeros(
                ts.params, depth=meta["stats_depth"]
            )
        (new_ts, metrics), _ = jax.lax.scan(
            body, (ts, m0), jnp.arange(width, dtype=jnp.int32)
        )
        return new_ts, metrics

    window.__name__ = f"fluxmpi_window_{width}"
    return jax.jit(
        window,
        in_shardings=(meta["state_in"], replicated, replicated, replicated),
        out_shardings=(meta["state_in"], replicated),
        donate_argnums=(0,) if meta["donate"] else (),
    )


def make_eval_step(
    metric_fn: Callable[[Any, Any, Any], Any],
    *,
    parallel: Any | None = None,
    mesh: Mesh | None = None,
    axis_name: str | None = None,
    state_sharding: Any | None = None,
    batch_spec: P | None = None,
    policy: Any | None = None,
) -> Callable[[TrainState, Any], Any]:
    """Build a compiled evaluation step: ``eval_step(state, batch) ->
    metrics``.

    ``metric_fn(params, model_state, batch)`` returns any pytree of metrics;
    reductions written over the global batch (``jnp.mean``/``sum``) are
    partitioned by XLA the same way the train step's loss is, so the returned
    metrics are already globally correct — no separate collective pass
    (the user-land eval loops of the reference's examples get the same
    treatment as training here).

    ``parallel`` / ``state_sharding`` / ``batch_spec`` mirror
    :func:`make_train_step` so an FSDP/TP-sharded :class:`TrainState`
    evaluates in its training layout; ``policy`` casts the params to its
    compute dtype entering ``metric_fn``, same as training.
    """
    if parallel is not None:
        plan, mesh, axis_name, batch_spec, state_sharding = _plan_defaults(
            parallel, mesh, axis_name, batch_spec, state_sharding,
            "make_eval_step",
        )
    else:
        plan, mesh, axis_name, batch_spec = _installed_plan_defaults(
            mesh, axis_name, batch_spec
        )
    mesh = mesh or global_mesh()
    name = axis_name or config.DP_AXIS_NAME
    spec = P(name) if batch_spec is None else batch_spec

    def step(ts: TrainState, batch):
        params = ts.params if policy is None else policy.cast_to_compute(
            ts.params)
        with _kernel_layout(mesh, spec, plan):
            return metric_fn(params, ts.model_state, batch)

    replicated = NamedSharding(mesh, P())
    state_in = replicated if state_sharding is None else state_sharding
    batch_sharding = NamedSharding(mesh, spec)
    return jax.jit(
        step,
        in_shardings=(state_in, batch_sharding),
        out_shardings=replicated,
    )
