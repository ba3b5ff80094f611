"""Layout autotuner: ``init(parallel="auto")`` — enumerate, prune, trial, bank.

PR 15 made N-D layouts declarative (one :class:`ParallelConfig` → one
mesh + strict partition rules) but a human still picked ``dp × fsdp ×
tp`` per model and pod shape, and the choice is workload-dependent,
not guessable. This module closes ROADMAP open item 3: a four-stage
search that needs no human in the loop and no framework coupling beyond
the one ``init`` kwarg.

Stage 1 — **enumerate** (:func:`enumerate_candidates`): every ordered
``dp × fsdp × tp`` factorization of the device count. ``pp``/``sp``/
``ep`` are out of the v1 search space on purpose — both need model
surgery (staged apply / attention-fn wiring) no generic trial can
perform; pin those by hand (docs/performance.md, "Auto layout").
Validity is *inherited*, not re-implemented: each candidate resolves
through :meth:`ParallelConfig.resolve` (axes must cover the devices)
and lays the params out through the plan's own strict rule path — a
``tp`` candidate whose Megatron table had to warn-and-degrade (a dim
the axis does not divide) is invalid, as is an ``fsdp`` candidate whose
ZeRO rule claimed nothing (every leaf under ``fsdp_min_size``).

Stage 2 — **prune without executing**: a static per-layout memory model
(:func:`layout_bytes` — param + optax-state + gradient bytes per device
from the same leaf walk the checkpoint manifest uses) checked against
the memory plane's ``bytes_limit``, then a relative compute/comms score
from the AOT-lowered update step's XLA cost analysis
(:func:`~fluxmpi_tpu.utils.flops.executable_cost` — ``lower().compile()``
reads only avals: nothing is placed, nothing runs). Memory-infeasible
candidates die first (``pruned="memory"``), then everything the static
ranking places past the trial budget (``pruned="dominated"``) — with
the pure-dp baseline always kept for the trials to beat.

Stage 3 — **profile** (:func:`_run_trial`): each survivor (≤
``FLUXMPI_TPU_AUTOTUNE_TRIALS``, default 4) runs short fused-window
trials through the real ``train_loop(fuse="window")`` machinery on
seeded synthetic batches — a warmup epoch pays the window compile
(booked to the goodput compile bucket and attributed by the compile
monitor, exactly like production), then a timed run that must be a pure
window-cache hit: zero steady-state retraces, zero new compiles. The
throughput winner is selected.

Stage 4 — **bank**: winner + the full candidate table become a schema'd
``fluxmpi_tpu.autotune/v1`` record — validated before it is trusted —
kept in-process, optionally in the ``FLUXMPI_TPU_AUTOTUNE_BANK`` JSON
file, and written next to the checkpoint manifest by every
``save_checkpoint`` under an autotuned plan. A later ``autotune()``
with the same (model fingerprint, topology) reuses the banked winner
and skips the trials entirely; a topology change (elastic resume onto a
different slice) misses the bank and re-tunes instead of crashing.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
import warnings
from typing import Any, Sequence

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..telemetry.schema import (
    AUTOTUNE_PRUNE_REASONS,
    AUTOTUNE_SCHEMA,
    validate_autotune_record,
)
from .plan import ParallelConfig, ResolvedPlan

__all__ = [
    "AutotuneResult",
    "autotune",
    "clear_bank",
    "enumerate_candidates",
    "layout_bytes",
    "model_fingerprint",
]

TRIALS_ENV = "FLUXMPI_TPU_AUTOTUNE_TRIALS"
BANK_ENV = "FLUXMPI_TPU_AUTOTUNE_BANK"

_DEFAULT_TRIALS = 4

# Score weighting: one HBM byte accessed costs about as much as four
# FLOPs at the arithmetic intensity where TPU matmuls stop being
# compute-bound — heavier traffic (all-gathers, reduce-scatters the
# partitioner inserted) should lose to an equal-FLOPs layout that keeps
# data local. The score only RANKS candidates of one model on one
# topology, so the constant's absolute calibration does not matter.
_BYTE_COST_FLOPS = 4.0

# In-process bank: (model fingerprint, topology key) → banked record.
# Survives shutdown()/init() cycles on purpose — re-tuning because a
# test re-initialized the runtime would make every auto run pay the
# trials twice in one process.
_BANK: dict[tuple[str, str], dict[str, Any]] = {}

# The record of the last completed (or bank-reused) tune in this
# process — what save_checkpoint's sidecar write reads.
_LAST_RECORD: dict[str, Any] | None = None


class Candidate:
    """One enumerated layout: its axes, resolved plan, and the evidence
    the stages attach (memory, static score, trial result, prune
    reason)."""

    def __init__(self, axes: dict[str, int], plan: ResolvedPlan):
        self.axes = axes
        self.plan = plan
        self.mem_bytes_per_device: int | None = None
        self.flops: float | None = None
        self.bytes_accessed: float | None = None
        self.score: float | None = None
        self.pruned: str | None = None
        self.trial: dict[str, Any] | None = None

    def describe(self) -> dict[str, Any]:
        return {
            "axes": dict(self.axes),
            "mem_bytes_per_device": self.mem_bytes_per_device,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "score": self.score,
            "pruned": self.pruned,
            "trial": self.trial,
        }


class AutotuneResult:
    """What :func:`autotune` returns: the winning resolved plan (carrying
    ``autotune_fingerprint``), the schema'd record, and whether the bank
    answered (``from_bank=True`` → zero trials ran)."""

    def __init__(
        self, plan: ResolvedPlan, record: dict[str, Any], from_bank: bool
    ):
        self.plan = plan
        self.record = record
        self.from_bank = from_bank

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        axes = ", ".join(
            f"{a}={s}" for a, s in self.record["winner"]["axes"].items()
            if s != 1
        )
        src = "bank" if self.from_bank else "trials"
        return f"AutotuneResult({axes or 'dp=1'}, from {src})"


# ---------------------------------------------------------------------------
# Identity: what makes a banked winner reusable.
# ---------------------------------------------------------------------------


def model_fingerprint(params: Any) -> str:
    """Stable identity of a model's parameter tree: sha256 over the
    manifest-style leaf walk (path, shape, dtype per leaf — the same
    ingredients the checkpoint manifest records), truncated to 16 hex
    chars. Two models with identical structure tune identically, so
    this — with the topology — is the bank key."""
    from .sharding import _path_str

    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()) or ())
        dtype = str(getattr(leaf, "dtype", "?"))
        rows.append(f"{_path_str(path)}:{shape}:{dtype}")
    digest = hashlib.sha256("\n".join(rows).encode("utf-8")).hexdigest()
    return digest[:16]


def topology_signature(devices: Sequence[jax.Device]) -> dict[str, Any]:
    """The topology half of the bank key: device count, kind, and the
    process world — what an elastic resume can change."""
    devs = list(devices)
    return {
        "n_devices": len(devs),
        "device_kind": str(devs[0].device_kind) if devs else "none",
        "process_count": int(jax.process_count()),
    }


def _topology_key(sig: dict[str, Any]) -> str:
    return (
        f"{sig['n_devices']}x{sig['device_kind']}"
        f"x{sig['process_count']}proc"
    )


# ---------------------------------------------------------------------------
# Stage 1: enumerate.
# ---------------------------------------------------------------------------


def _factorizations(n: int) -> list[tuple[int, int, int]]:
    """All ordered (dp, fsdp, tp) triples of positive ints with product
    ``n`` — deterministic order (dp descending: pure-dp first, the
    layout most likely to win at small scale trials first)."""
    out = []
    for dp in range(n, 0, -1):
        if n % dp:
            continue
        rest = n // dp
        for fsdp in range(rest, 0, -1):
            if rest % fsdp:
                continue
            out.append((dp, fsdp, rest // fsdp))
    return out


def enumerate_candidates(
    params: Any,
    devices: Sequence[jax.Device],
    *,
    fsdp_min_size: int = 1024,
) -> list[Candidate]:
    """Stage 1: every valid ``dp × fsdp × tp`` layout for this model on
    these devices. Validity rides the existing strict plan path — each
    candidate resolves through :meth:`ParallelConfig.resolve` and lays
    the params out through ``plan.partition_specs``; a candidate whose
    rules had to warn-and-degrade (tp axis not dividing a matched dim)
    or whose fsdp/tp axis claimed no leaf at all is dropped, so
    no-silent-replication is inherited rather than re-implemented."""
    devs = list(devices)
    out: list[Candidate] = []
    for dp, fsdp, tp in _factorizations(len(devs)):
        cfg = ParallelConfig(
            dp=dp, fsdp=fsdp, tp=tp, fsdp_min_size=fsdp_min_size
        )
        try:
            plan = cfg.resolve(devs)
        except Exception:
            continue
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            try:
                plan.partition_specs(params)
            except Exception:
                continue
        if caught:
            # The rule engine degraded something (a tp dim the axis
            # does not divide, a rank mismatch): this layout would
            # silently under-shard — not a candidate.
            continue
        if tp > 1 and not plan.rule_hits.get("tp"):
            continue
        if fsdp > 1 and not plan.rule_hits.get("fsdp"):
            # Every leaf under fsdp_min_size: the axis buys no memory,
            # only collective latency.
            continue
        out.append(Candidate({"dp": dp, "fsdp": fsdp, "tp": tp}, plan))
    return out


# ---------------------------------------------------------------------------
# Stage 2: prune without executing.
# ---------------------------------------------------------------------------


def _spec_shard_factor(spec: Any, mesh: Any) -> int:
    factor = 1
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        for name in names:
            factor *= int(mesh.shape[name])
    return factor


def _tree_bytes_per_device(tree: Any, specs: Any, mesh: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    total = 0
    for leaf, spec in zip(leaves, spec_leaves):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        total += -(-nbytes // _spec_shard_factor(spec, mesh))
    return int(total)


def state_template(
    params: Any, optimizer: Any, model_state: Any = None
) -> Any:
    """Abstract :class:`~fluxmpi_tpu.parallel.TrainState` for the memory
    model: ``jax.eval_shape`` over ``TrainState.create`` — the optax
    state's structure and dtypes without allocating a byte of it."""
    from .train import TrainState

    return jax.eval_shape(
        lambda: TrainState.create(params, optimizer, model_state)
    )


def layout_bytes(template: Any, plan: ResolvedPlan) -> int:
    """Stage 2's static memory model: steady-state training bytes per
    device under ``plan`` — the sharded :class:`TrainState` (params +
    optimizer state, laid out by the plan's own rule) plus one gradient
    tree (same layout as the params). Activations and batch staging are
    excluded (both scale with the batch the caller controls, not the
    layout) — the check against ``bytes_limit`` is a floor, which is
    exactly what infeasibility pruning needs."""
    mesh = plan.mesh
    state_specs = plan.partition_specs(template)
    total = _tree_bytes_per_device(template, state_specs, mesh)
    params = getattr(template, "params", None)
    if params is not None:
        total += _tree_bytes_per_device(
            params, plan.partition_specs(params), mesh
        )
    return total


def _sharded_avals(tree: Any, specs: Any, mesh: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    avals = [
        jax.ShapeDtypeStruct(
            tuple(leaf.shape),
            leaf.dtype,
            sharding=NamedSharding(mesh, spec),
        )
        for leaf, spec in zip(leaves, spec_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, avals)


def _static_cost(
    loss_fn: Any,
    optimizer: Any,
    template: Any,
    sample_batch: Any,
    plan: ResolvedPlan,
) -> dict[str, float] | None:
    """AOT-lower one full update step (grad + optimizer apply) under the
    candidate's shardings and read XLA's cost analysis — per-device
    FLOPs and bytes accessed, communication the partitioner inserted
    included. ``lower().compile()`` consumes only avals: no data is
    placed on the candidate's mesh and nothing executes.

    Pallas kernels (the flash-attention hot path) lower to opaque custom
    calls whose matmuls XLA's cost model reports as zero, so the traced
    jaxpr is walked for ``pallas_call`` equations and their analytic
    cost (:func:`~fluxmpi_tpu.utils.flops.pallas_kernel_cost`) is folded
    in, divided evenly across the mesh — attention work shards with the
    batch/heads under every dp×fsdp×tp candidate, so the per-device
    share is layout-invariant but the TOTAL is real: a kernel-heavy
    model no longer looks computation-free next to its communication."""
    import optax

    from ..utils.flops import executable_cost, pallas_kernel_cost

    mesh = plan.mesh
    state_avals = _sharded_avals(
        template, plan.partition_specs(template), mesh
    )
    batch_spec = plan.batch_spec
    batch_avals = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            tuple(np.shape(x)),
            getattr(x, "dtype", np.float32),
            sharding=NamedSharding(mesh, batch_spec),
        ),
        sample_batch,
    )

    def update(state, batch):
        def scalar_loss(p):
            loss, _ = loss_fn(p, state.model_state, batch)
            return loss

        grads = jax.grad(scalar_loss)(state.params)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return state.replace(
            params=params, opt_state=opt_state, step=state.step + 1
        )

    try:
        compiled = jax.jit(update).lower(state_avals, batch_avals).compile()
    except Exception:
        return None
    cost = executable_cost(compiled)
    if cost is not None:
        try:
            kernel = pallas_kernel_cost(
                jax.make_jaxpr(update)(state_avals, batch_avals)
            )
        except Exception:  # pragma: no cover - cost stays XLA-only
            kernel = None
        if kernel:
            ndev = float(mesh.devices.size) or 1.0
            cost["flops"] += kernel["flops"] / ndev
            cost["bytes_accessed"] += kernel["bytes_accessed"] / ndev
    return cost


def _score(cost: dict[str, float] | None) -> float | None:
    if not cost:
        return None
    flops = cost.get("flops") or 0.0
    bytes_accessed = cost.get("bytes_accessed") or 0.0
    if flops <= 0 and bytes_accessed <= 0:
        return None
    return flops + _BYTE_COST_FLOPS * bytes_accessed


def _prune(
    candidates: list[Candidate], *, bytes_limit: int | None, max_trials: int
) -> list[Candidate]:
    """Stage 2's verdict. Memory-infeasible layouts die first
    (``pruned="memory"``); the rest are ranked by the static cost score
    (ties broken by the memory floor, then axes — deterministic) and
    everything past the trial budget is ``pruned="dominated"``. The
    pure-dp layout, when feasible, is always among the survivors: it is
    the zero-collective baseline every other layout must beat on the
    clock, and the static score — a relative model, not a measurement —
    must not be allowed to silence it. Returns the survivors
    best-score-first."""
    for cand in candidates:
        if (
            bytes_limit
            and cand.mem_bytes_per_device is not None
            and cand.mem_bytes_per_device > bytes_limit
        ):
            cand.pruned = "memory"
    alive = [c for c in candidates if c.pruned is None]

    def sort_key(c: Candidate) -> tuple:
        return (
            c.score if c.score is not None else float("inf"),
            c.mem_bytes_per_device or 0,
            tuple(sorted(c.axes.items())),
        )

    alive.sort(key=sort_key)
    survivors = alive[:max_trials]
    pure_dp = next(
        (
            c
            for c in alive
            if all(s == 1 for a, s in c.axes.items() if a != "dp")
        ),
        None,
    )
    if pure_dp is not None and pure_dp not in survivors:
        survivors[-1] = pure_dp
    for cand in alive:
        if cand not in survivors:
            cand.pruned = "dominated"
    return survivors


# ---------------------------------------------------------------------------
# Stage 3: profile — fused-window trials on the real train_loop.
# ---------------------------------------------------------------------------


def _trial_dataset(sample_batch: Any, window: int, seed: int) -> Any:
    """``window`` seeded shuffles of the sample batch, concatenated —
    every candidate trains on the identical synthetic stream."""
    rng = np.random.default_rng(seed)
    lead = int(np.shape(jax.tree_util.tree_leaves(sample_batch)[0])[0])
    perms = [rng.permutation(lead) for _ in range(window)]
    return jax.tree_util.tree_map(
        lambda x: np.concatenate([np.asarray(x)[p] for p in perms]),
        sample_batch,
    )


def _run_trial(
    loss_fn: Any,
    optimizer: Any,
    host_params: Any,
    model_state: Any,
    sample_batch: Any,
    plan: ResolvedPlan,
    *,
    window: int,
    epochs: int,
    seed: int,
) -> dict[str, Any]:
    """One candidate's fused-window trial: place a fresh state under the
    plan, build the real ``make_train_step(parallel=plan)``, and drive
    ``train_loop(fuse="window")`` twice — a warmup epoch that pays the
    window AOT compile (booked to the goodput compile bucket and
    attributed by the compile monitor, like any production run), then
    the timed epochs, which must be a pure window-cache hit: zero new
    compiles, zero steady-state retraces. This is the module's ONE trial
    entry point — tests monkeypatch it (explode to prove a bank hit ran
    no trial; stub to make winner selection deterministic)."""
    from ..data import ArrayDataset, DistributedDataLoader
    from ..telemetry.compileplane import get_compile_monitor
    from .loop import train_loop
    from .train import TrainState, make_train_step, replicate

    t0 = time.perf_counter()
    gbs = int(np.shape(jax.tree_util.tree_leaves(sample_batch)[0])[0])
    dataset = ArrayDataset(_trial_dataset(sample_batch, window, seed))
    axes = plan.data_axes
    loader = DistributedDataLoader(
        dataset,
        gbs,
        mesh=plan.mesh,
        axis_name=axes[0] if len(axes) == 1 else list(axes),
    )

    def fresh_state():
        state = TrainState.create(host_params, optimizer, model_state)
        if plan.shards_parameters:
            state, _ = plan.shard_state(state)
        else:
            state = replicate(state, plan.mesh)
        return state

    # First placement banks the layout on the plan (shard_state), which
    # make_train_step(parallel=plan) requires for sharding plans — so
    # the state comes before the step.
    state0 = fresh_state()
    step = make_train_step(loss_fn, optimizer, parallel=plan)
    cp = get_compile_monitor()
    if cp is not None:
        cp.reset_run()
    _, warm = train_loop(
        step, state0, loader, epochs=1, fuse="window",
        flush_every=window, metrics=False,
    )
    if cp is not None:
        cp.reset_run()  # the timed run's retrace ledger starts clean
    _, timed = train_loop(
        step, fresh_state(), loader, epochs=epochs, fuse="window",
        flush_every=window, metrics=False,
    )
    cache = timed.get("window_cache") or {}
    retraces = len(cp.retraces) if cp is not None else None
    return {
        "examples_per_sec": round(float(timed["examples_per_sec"]), 3),
        "updates": int(timed["updates"]),
        "compile_seconds": round(
            float(warm.get("window_compile_seconds") or 0.0), 4
        ),
        "steady_compiles": int(cache.get("misses", 0)),
        "retraces": retraces,
        "seconds": round(time.perf_counter() - t0, 3),
    }


# ---------------------------------------------------------------------------
# Stage 4: bank.
# ---------------------------------------------------------------------------


def _bank_path(bank: Any) -> str | None:
    if isinstance(bank, str) and bank:
        return bank
    if bank is None:
        path = os.environ.get(BANK_ENV, "").strip()
        return path or None
    return None


def _bank_lookup(
    fingerprint: str, topo_key: str, bank: Any
) -> dict[str, Any] | None:
    rec = _BANK.get((fingerprint, topo_key))
    if rec is not None:
        return rec
    path = _bank_path(bank)
    if path and os.path.exists(path):
        try:
            with open(path, "r", encoding="utf-8") as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None
        if (
            isinstance(rec, dict)
            and rec.get("model_fingerprint") == fingerprint
            and _topology_key(rec.get("topology") or {}) == topo_key
            and not validate_autotune_record(rec)
        ):
            return rec
    return None


def _bank_store(record: dict[str, Any], bank: Any) -> None:
    key = (record["model_fingerprint"], _topology_key(record["topology"]))
    _BANK[key] = record
    path = _bank_path(bank)
    if path:
        try:
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(record, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError as exc:
            warnings.warn(
                f"could not write the autotune bank at {path} ({exc!r}); "
                f"the winner stays usable in-process, a later run re-tunes",
                stacklevel=2,
            )


def clear_bank() -> None:
    """Drop every in-process banked winner (test helper — file banks are
    the caller's to remove)."""
    global _LAST_RECORD
    _BANK.clear()
    _LAST_RECORD = None


def last_record() -> dict[str, Any] | None:
    """The record of this process's most recent tune (or bank reuse) —
    what the checkpoint sidecar write reads. None before any."""
    return _LAST_RECORD


def write_bank_sidecar(path: str) -> bool:
    """Write the last tune's record as ``<path>.autotune.json`` next to
    the checkpoint manifest — but only when the runtime's installed plan
    IS that tune's winner (a hand-pinned plan must not inherit another
    layout's evidence). Returns True when a sidecar was written."""
    from ..runtime import global_plan

    record = _LAST_RECORD
    if record is None:
        return False
    plan = global_plan()
    if plan is None or getattr(plan, "autotune_fingerprint", None) != (
        record["model_fingerprint"]
    ):
        return False
    target = path + ".autotune.json"
    with open(target, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    return True


# ---------------------------------------------------------------------------
# Observability: autotune.* gauges + the AUTOTUNE /status board.
# ---------------------------------------------------------------------------


def _post_observability(record: dict[str, Any], from_bank: bool) -> None:
    from ..telemetry import get_registry
    from ..telemetry import export as _export

    pruned: dict[str, int] = {reason: 0 for reason in AUTOTUNE_PRUNE_REASONS}
    best = None
    for cand in record["candidates"]:
        if cand["pruned"] in pruned:
            pruned[cand["pruned"]] += 1
        trial = cand.get("trial")
        if trial and (best is None or trial["examples_per_sec"] > best):
            best = trial["examples_per_sec"]
    trial_seconds = sum(
        (c.get("trial") or {}).get("seconds") or 0.0
        for c in record["candidates"]
    )
    registry = get_registry()
    registry.gauge("autotune.candidates_total").set(
        float(len(record["candidates"]))
    )
    for reason, count in pruned.items():
        registry.gauge("autotune.pruned", reason=reason).set(float(count))
    registry.gauge("autotune.trials").set(float(record["trials"]))
    registry.gauge("autotune.trial_seconds").set(float(trial_seconds))
    if from_bank:
        registry.counter("autotune.bank_hits").inc()
    exporter = _export.get_exporter()
    if exporter is not None and exporter.enabled:
        exporter.note_autotune(
            fingerprint=record["model_fingerprint"],
            winner=dict(record["winner"]["axes"]),
            candidates=len(record["candidates"]),
            pruned_memory=pruned.get("memory", 0),
            pruned_dominated=pruned.get("dominated", 0),
            trials=record["trials"],
            best_examples_per_sec=best,
            bank="hit" if from_bank else "tuned",
        )


# ---------------------------------------------------------------------------
# The entry point.
# ---------------------------------------------------------------------------


def _plan_from_record(
    record: dict[str, Any], devices: Sequence[jax.Device]
) -> ResolvedPlan:
    axes = {
        axis: int(size)
        for axis, size in record["winner"]["axes"].items()
        if axis in ("dp", "fsdp", "tp")
    }
    plan = ParallelConfig(
        **axes, fsdp_min_size=int(record["fsdp_min_size"])
    ).resolve(list(devices))
    plan.autotune_fingerprint = record["model_fingerprint"]
    return plan


def _trials_budget(trials: int | None) -> int:
    if trials is not None:
        return max(1, int(trials))
    raw = os.environ.get(TRIALS_ENV, "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            warnings.warn(
                f"ignoring {TRIALS_ENV}={raw!r} (not an int); using the "
                f"default {_DEFAULT_TRIALS}",
                stacklevel=3,
            )
    return _DEFAULT_TRIALS


def autotune(
    loss_fn: Any,
    optimizer: Any,
    params: Any,
    sample_batch: Any,
    *,
    model_state: Any = None,
    devices: Sequence[jax.Device] | None = None,
    trials: int | None = None,
    window: int = 4,
    trial_epochs: int = 2,
    fsdp_min_size: int = 1024,
    bytes_limit: int | None = None,
    bank: Any = None,
    seed: int = 0,
    force: bool = False,
) -> AutotuneResult:
    """Search the layout space for (this model, this topology) and bank
    the winner. Under ``init(parallel="auto")`` the winning plan is also
    installed as the global plan, so ``make_train_step(parallel="auto")``
    and the loader defaults pick it up with no further wiring.

    Args:
      loss_fn: the training loss ``(params, model_state, batch) ->
        (loss, new_model_state)`` — the same callable
        :func:`make_train_step` takes; trials train with it.
      optimizer: the optax transformation trials (and the static memory
        model's optimizer-state accounting) use.
      params: the model's parameter pytree (host or device arrays) —
        fingerprinted for the bank key, walked by the rule engine.
      sample_batch: one host batch (pytree of arrays, leading dim the
        GLOBAL batch size — must divide by the device count so every
        candidate shards it evenly). Trials train on ``window`` seeded
        shuffles of it; the AOT cost model lowers against its avals.
      model_state: mutable model state for ``TrainState.create``.
      devices: topology to tune for (default: the runtime mesh's
        devices when initialized, else all of ``jax.devices()``). A
        DIFFERENT device set than a banked record's re-tunes — that is
        the elastic-resume contract.
      trials: trial budget cap (default ``FLUXMPI_TPU_AUTOTUNE_TRIALS``
        or 4) — stage 2 prunes down to at most this many survivors.
      window / trial_epochs: fused-window width and timed epochs per
        trial (small on purpose — compile dominates a trial; throughput
        ranking stabilizes within a few windows).
      fsdp_min_size: forwarded to every candidate's
        :class:`ParallelConfig`.
      bytes_limit: per-device memory budget for stage 2 (default: the
        memory plane's ``bytes_limit`` stat, absent on CPU — no memory
        pruning there).
      bank: bank file path override (default ``FLUXMPI_TPU_AUTOTUNE_BANK``;
        the in-process bank always participates).
      seed: the synthetic-stream seed — fixed seed, deterministic
        candidate table and trial stream.
      force: re-tune even when the bank has a matching winner.

    Returns:
      :class:`AutotuneResult` — ``.plan`` (resolved, fingerprint-tagged),
      ``.record`` (the validated ``fluxmpi_tpu.autotune/v1`` table), and
      ``.from_bank``.
    """
    global _LAST_RECORD
    from .. import runtime as _runtime

    if devices is None:
        if _runtime.is_initialized():
            devices = list(_runtime.global_mesh().devices.flat)
        else:
            devices = jax.devices()
    devices = list(devices)
    if not devices:
        raise ValueError("autotune needs at least one device")
    lead = int(np.shape(jax.tree_util.tree_leaves(sample_batch)[0])[0])
    if lead % len(devices):
        raise ValueError(
            f"sample_batch leading dim {lead} must divide by the device "
            f"count {len(devices)} so every candidate layout shards it "
            f"evenly"
        )
    host_params = jax.device_get(params)
    fingerprint = model_fingerprint(host_params)
    topology = topology_signature(devices)
    topo_key = _topology_key(topology)

    if not force:
        banked = _bank_lookup(fingerprint, topo_key, bank)
        if banked is not None:
            plan = _plan_from_record(banked, devices)
            _LAST_RECORD = banked
            _post_observability(banked, from_bank=True)
            _runtime._install_autotuned_plan(plan)
            return AutotuneResult(plan, banked, from_bank=True)

    max_trials = _trials_budget(trials)
    candidates = enumerate_candidates(
        host_params, devices, fsdp_min_size=fsdp_min_size
    )
    if not candidates:
        raise RuntimeError(
            f"autotune found no valid layout for {len(devices)} device(s) "
            f"— the Megatron tp table matched nothing it can divide and "
            f"fsdp_min_size={fsdp_min_size} left nothing to shard; pin a "
            f"ParallelConfig by hand"
        )

    # Stage 2a: the static memory model, against the memory plane's
    # per-device budget when one is reported (CPU reports none).
    template = state_template(host_params, optimizer, model_state)
    if bytes_limit is None:
        from ..telemetry.memory import device_memory_stats

        stats = device_memory_stats(devices[0])
        limit = stats.get("bytes_limit")
        bytes_limit = int(limit) if limit else None
    for cand in candidates:
        cand.mem_bytes_per_device = layout_bytes(template, cand.plan)

    # Stage 2b: the AOT cost score — only for memory-feasible layouts
    # (lowering a layout the budget already killed is wasted compile).
    for cand in candidates:
        if bytes_limit and cand.mem_bytes_per_device > bytes_limit:
            continue
        cost = _static_cost(
            loss_fn, optimizer, template, sample_batch, cand.plan
        )
        if cost:
            cand.flops = cost.get("flops")
            cand.bytes_accessed = cost.get("bytes_accessed")
        cand.score = _score(cost)

    survivors = _prune(
        candidates, bytes_limit=bytes_limit, max_trials=max_trials
    )
    if not survivors:
        raise RuntimeError(
            f"every candidate layout exceeds the {bytes_limit}-byte "
            f"per-device budget — this model does not fit this topology "
            f"under dp×fsdp×tp alone (add pp by hand, or more devices)"
        )

    # Stage 3: fused-window trials on the real train_loop machinery.
    for cand in survivors:
        cand.trial = _run_trial(
            loss_fn, optimizer, host_params, model_state, sample_batch,
            cand.plan, window=window, epochs=trial_epochs, seed=seed,
        )
    winner = max(
        survivors,
        key=lambda c: (
            c.trial["examples_per_sec"],
            -(c.score or 0.0),
        ),
    )

    record = {
        "schema": AUTOTUNE_SCHEMA,
        "time_unix": time.time(),
        "model_fingerprint": fingerprint,
        "topology": topology,
        "fsdp_min_size": int(fsdp_min_size),
        "winner": {
            "axes": dict(winner.axes),
            "axis_names": dict(winner.plan.axis_names),
        },
        "trials": len(survivors),
        "candidates": [c.describe() for c in candidates],
    }
    errors = validate_autotune_record(record)
    if errors:  # pragma: no cover - producer drift guard
        raise ValueError(
            "autotune produced an invalid record: " + "; ".join(errors)
        )
    _bank_store(record, bank)
    _LAST_RECORD = record
    winner.plan.autotune_fingerprint = fingerprint
    _post_observability(record, from_bank=False)
    _runtime._install_autotuned_plan(winner.plan)
    return AutotuneResult(winner.plan, record, from_bank=False)
