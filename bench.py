"""Benchmark harness: prints ONE JSON line with the headline metric.

Flagship workload (BASELINE.md): ResNet-50 synthetic-ImageNet DP training
throughput in images/sec/chip (BASELINE config 3), with MFU, a loader-fed
variant (batches drawn through DistributedDataLoader + the C++ prefetcher,
host→device transfer on the measured path), a flash-vs-dense attention
comparison, and a DP scaling-efficiency measurement.

Timing discipline: every measurement (a) forces synchronization by
``device_get``-ing the scalar loss, and (b) uses a two-point slope — time
N1 steps and N2 steps, rate = (N2-N1)/(t2-t1) — so a fixed per-sync cost
cancels exactly.

No chip, no number: the parent stays off JAX (a chip belongs to one
process at a time) and starts one child per config. Before the workload
children, one child checks that JAX sees a TPU; its answer lands in the
output JSON under ``probe``. A run that was not told to use the CPU
(``FLUXMPI_TPU_BENCH_SMOKE=1``, ``FLUXMPI_TPU_BENCH_PLATFORM=cpu`` or
``JAX_PLATFORMS=cpu``) and finds no TPU exits non-zero — it never falls
back to a CPU number — and so does a run whose config or plan produced
no metric.

``vs_baseline``: the reference publishes no numbers (BASELINE.md
"published: {}"), so the ratio is against this repo's own recorded anchor,
keyed by (metric, platform, device fingerprint) so a number from another
machine is never presented as a regression ratio.

Env knobs:
  FLUXMPI_TPU_BENCH_CONFIG    force one config
                              (resnet50|cnn|mlp|attention|transformer|deq|
                              unet|serving|train_loop — unet, serving and
                              train_loop are forced-only, not in the
                              fallback plan; train_loop is what the
                              scaling and per-axis legs spawn)
  FLUXMPI_TPU_BENCH_PARALLEL  ParallelConfig for the train_loop child,
                              e.g. "dp=4,fsdp=2" (default dp=-1: all
                              visible devices data-parallel)
  FLUXMPI_TPU_BENCH_TIMEOUT   override per-config child timeout in seconds
  FLUXMPI_TPU_BENCH_BUDGET    overall wall budget in seconds (default 4200)
  FLUXMPI_TPU_BENCH_PLATFORM  pin jax_platforms in children ("cpu" is an
                              explicit request for a CPU run)
  FLUXMPI_TPU_BENCH_DEVICES   child uses only the first N devices
  FLUXMPI_TPU_COMPILE_CACHE   persistent XLA compile cache dir
  FLUXMPI_TPU_BENCH_JSONL     also emit results through the telemetry
                              JSONL sink at this path (schema-validated
                              by scripts/check_metrics_schema.py)
  FLUXMPI_TPU_BENCH_STEPS     cap the measured steps per workload (smoke /
                              quick-iteration knob; slope timing keeps
                              working down to a handful of steps)
  FLUXMPI_TPU_BENCH_SMOKE     "1" = smoke mode: skip the TPU check, run
                              the mlp config + the cpu-virtual scaling
                              pair with tiny budgets on CPU, print the
                              same JSON shape. Runs inside tier-1 CI
                              (tests/test_bench.py) so bench/schema
                              breakage is caught before a round.
  FLUXMPI_TPU_BENCH_TRACE_DIR enable span tracing in each bench child and
                              export a Chrome-trace JSON per config into
                              this directory (trace.<config>.json —
                              merge with scripts/merge_traces.py).
                              FLUXMPI_TPU_TRACE / FLUXMPI_TPU_WATCHDOG
                              themselves also pass through to children
                              (the overhead-budget check runs the mlp
                              config with both enabled).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# (config name, default child timeout seconds) in fallback order.
_CONFIGS: tuple[tuple[str, float], ...] = (
    ("resnet50", 900.0),
    ("cnn", 300.0),
    ("mlp", 150.0),
)
# The child that checks for a TPU: interpreter start, jax import, backend
# init and one tiny matmul.
_PROBE_TIMEOUT_S = 120.0
_DEFAULT_BUDGET_S = 4200.0

# First real recorded number per (metric, platform, device fingerprint) —
# the vs_baseline anchor. TPU anchor recorded 2026-07-29, first healthy-chip
# round (slope-timed, device_get-synced); CPU anchors from the round-2 build
# host (1-core container, 8 virtual devices).
_ANCHORS: dict[tuple[str, str, str], float] = {
    ("resnet50_images_per_sec_per_chip", "tpu", "TPU v5 lite"): 2509.5,
    ("transformer_lm_tokens_per_sec_per_chip", "tpu", "TPU v5 lite"): 107622.4,
    ("mlp_quickstart_samples_per_sec_per_chip", "cpu", "cpu1"): 84080.6,
    ("cifar_cnn_images_per_sec_per_chip", "cpu", "cpu1"): 319.3,
}

# FLOPs/MFU accounting lives in fluxmpi_tpu.utils.flops (promoted out of
# this file so the live run-health plane computes MFU with the SAME peak
# table and formula the bench reports). The delegates below import it
# lazily: the parent driver must stay off jax — `import fluxmpi_tpu`
# initializes the backend, and a parent that holds the chip starves its
# children.


def _chip_peak_flops(device_kind: str) -> float | None:
    from fluxmpi_tpu.utils.flops import chip_peak_flops

    return chip_peak_flops(device_kind)


def _device_fingerprint(platform: str, device_kind: str) -> str:
    """Anchor key component: the device kind on accelerators; on CPU the
    core count too (throughput scales with it across hosts)."""
    if platform == "cpu":
        return f"cpu{os.cpu_count()}"
    return device_kind


def _anchor_for(metric: str) -> float | None:
    import jax

    platform = jax.default_backend()
    fp = _device_fingerprint(platform, jax.devices()[0].device_kind)
    return _ANCHORS.get((metric, platform, fp))


def _enable_compilation_cache() -> None:
    """Persist compiled XLA programs so repeat bench runs skip the (slow)
    first compile — delegated to the ONE runtime implementation
    (:func:`fluxmpi_tpu.runtime.enable_compile_cache`, the same knob
    ``init(compile_cache=)`` / ``FLUXMPI_TPU_COMPILE_CACHE`` wire for
    training runs; ``JAX_COMPILATION_CACHE_DIR`` places it). TPU only;
    the helper documents why XLA:CPU persistence is unsafe."""
    from fluxmpi_tpu.runtime import enable_compile_cache

    enable_compile_cache()


def _sync(x) -> None:
    """Force device completion by copying the (scalar) value to the
    host."""
    import jax

    np.asarray(jax.device_get(x))


def _sync_each_step() -> bool:
    """On CPU (virtual 8-device meshes), back-to-back async dispatch of
    donating collective programs can interleave run instances on the
    shared thread pool and wedge XLA:CPU's in-process rendezvous (observed:
    7/8 participants arrive, 40 s kill timer). A per-step sync serializes
    launches; on TPU the async loop stands."""
    import jax

    return jax.default_backend() != "tpu"


def _timed_steps(step, state, data, n: int):
    per_step = _sync_each_step()
    t0 = time.perf_counter()
    loss = None
    for _ in range(n):
        state, loss = step(state, data)
        if per_step:
            _sync(loss)
    _sync(loss)
    return time.perf_counter() - t0, state


def _steps_per_sec(step, state, data, warmup: int, steps: int):
    """Slope-timed steps/second: two measurements of different length so the
    fixed per-sync host↔device round trip cancels. The state is carried
    because the compiled step donates its input buffers."""
    per_step = _sync_each_step()
    loss = None
    for _ in range(warmup):
        state, loss = step(state, data)
        if per_step:
            _sync(loss)
    if loss is not None:
        _sync(loss)
    n1 = max(2, steps // 5)
    t1, state = _timed_steps(step, state, data, n1)
    t2, state = _timed_steps(step, state, data, steps)
    if t2 > t1:
        rate = (steps - n1) / (t2 - t1)
    else:  # degenerate clock resolution; fall back to the longer run
        rate = steps / t2
    return rate, state


def _dispatch_probe(mesh) -> dict | None:
    """Per-dispatch host cost of a trivial jitted program over the mesh —
    the null-step floor under every train step. Slope-timed chained
    dispatches (the chain serializes on data dependence, so the measured
    cost is enqueue + scheduling, not compute). This is the number that
    grows with device count and that scan_steps/pipelining amortize; it
    makes the synthetic-vs-dispatch gap attributable in one run."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from fluxmpi_tpu import config as fm_config

        n_dev = int(np.prod(list(mesh.shape.values())))
        axis = (
            fm_config.DP_AXIS_NAME
            if fm_config.DP_AXIS_NAME in mesh.shape
            else tuple(mesh.shape)[0]
        )
        x = jax.device_put(
            jnp.zeros((n_dev,), jnp.float32), NamedSharding(mesh, P(axis))
        )
        bump = jax.jit(lambda v: v + 1.0)
        _sync(bump(x))  # compile outside the timed region

        def run(n: int) -> float:
            t0 = time.perf_counter()
            y = x
            for _ in range(n):
                y = bump(y)
            _sync(y[0])
            return time.perf_counter() - t0

        n1, n2 = 30, 150
        t1, t2 = run(n1), run(n2)
        per = (t2 - t1) / (n2 - n1) if t2 > t1 else t2 / n2
        return {"per_dispatch_us": round(per * 1e6, 1), "n_dev": n_dev}
    except Exception as exc:  # pragma: no cover - diagnostics only
        print(f"bench: dispatch probe failed: {exc!r}", file=sys.stderr)
        return None


def _cost_analysis_flops(step, state, data) -> float | None:
    """FLOPs per compiled step straight from XLA's cost model, if exposed
    (delegates to the shared helper the live goodput plane also uses)."""
    from fluxmpi_tpu.utils.flops import cost_analysis_flops

    return cost_analysis_flops(step, state, data)


def _raw_mfu(
    flops_per_step: float | None, rate: float, n_dev: int, device_kind: str
) -> float | None:
    from fluxmpi_tpu.utils.flops import mfu

    return mfu(flops_per_step, rate, n_dev, device_kind)


def _discard_impossible(mfu: float | None) -> tuple[float | None, bool]:
    """The ONE discard policy for impossible MFU (>1.0: a broken clock
    or FLOPs estimate, never real): ``(value_or_None, discarded)``."""
    if mfu is not None and mfu > 1.0:
        print(f"bench: discarding impossible MFU {mfu:.2f}", file=sys.stderr)
        return None, True
    return mfu, False


def _mfu(
    flops_per_step: float | None, rate: float, n_dev: int, device_kind: str
) -> float | None:
    """Model FLOPs utilization per chip: FLOPs/step × steps/sec ÷
    (chips × peak). Returns None when peak is unknown or the number is
    impossible — callers wanting the discard *recorded* take the flag
    from ``_discard_impossible`` and bank ``mfu_discarded`` (see
    ``_bench_workload``)."""
    value, _ = _discard_impossible(
        _raw_mfu(flops_per_step, rate, n_dev, device_kind)
    )
    return value


def _visible_devices():
    """jax.devices(), optionally truncated to FLUXMPI_TPU_BENCH_DEVICES —
    the submesh hook the scaling-efficiency mode uses."""
    import jax

    devs = jax.devices()
    limit = os.environ.get("FLUXMPI_TPU_BENCH_DEVICES")
    if limit:
        devs = devs[: int(limit)]
    return devs


def _bench_workload(
    *,
    make_model_batch,
    stateful: bool,
    metric_name: str,
    unit: str,
    steps: int,
    ndigits: int,
    analytic_flops_per_sample: float | None = None,
    loader_fed: bool = False,
    value_scale: float = 1.0,
    init_fn=None,
    default_scan_steps: int = 1,
    fused_ab: bool = False,
):
    """Shared harness: synthetic batch → compiled DP train step → per-chip
    throughput. ``make_model_batch(n_dev)`` returns
    ``(model, x, y, loss_fn_factory, optimizer)`` where ``loss_fn_factory``
    builds the ``(params, model_state, batch)`` loss for that model."""
    import jax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    devs = _visible_devices()
    mesh = fm.init(devices=devs)
    n_dev = fm.total_workers()
    device_kind = devs[0].device_kind
    model, x, y, loss_fn, optimizer = make_model_batch(n_dev)

    if init_fn is not None:
        # Models whose __call__ is not (x, train=) shaped (e.g. the UNet's
        # (x, t)) bring their own initializer.
        params = init_fn()
        model_state = None
    elif stateful:
        variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
        params = variables["params"]
        model_state = variables.get("batch_stats")
    else:
        params = model.init(jax.random.PRNGKey(0), x[:2])
        model_state = None

    # Tuning knobs (VERDICT r5 perf session): FLUXMPI_TPU_BENCH_REMAT=1
    # turns on rematerialization; FLUXMPI_TPU_BENCH_SCAN_STEPS=K compiles
    # K sequential updates into one dispatch (make_train_step scan_steps)
    # — isolates host dispatch latency from device time. Rates and
    # FLOPs below are per CALL, so K scales both.
    remat_env = os.environ.get("FLUXMPI_TPU_BENCH_REMAT", "0")
    remat = "dots" if remat_env == "dots" else remat_env == "1"
    scan = max(1, int(os.environ.get(
        "FLUXMPI_TPU_BENCH_SCAN_STEPS", str(default_scan_steps)
    )))
    if scan > 1:
        # Keep measured wall time roughly constant: each call is scan
        # updates, so fewer calls cover the same optimizer-step count.
        # Floor of 10 calls: the two-point slope needs enough calls per
        # leg or run-to-run variance swamps the measurement.
        steps = max(10, steps // scan)
    cap = os.environ.get("FLUXMPI_TPU_BENCH_STEPS")
    if cap:
        steps = max(2, min(steps, int(cap)))
    step = make_train_step(loss_fn, optimizer, mesh=mesh, style="auto",
                           remat=remat)
    # Host copies for the fused A/B's fresh states: the timed steps
    # donate the replicated state, and replicate() may alias device
    # inputs — building a second TrainState from consumed params would
    # hit deleted arrays.
    host_params = jax.device_get(params) if fused_ab else None
    state = replicate(TrainState.create(params, optimizer, model_state), mesh)
    data = shard_batch((x, y), mesh)

    # Cost analysis first: it lowers/compiles without executing, so it must
    # see the state before the donating timed steps consume its buffers.
    xla_flops = _cost_analysis_flops(step, state, data)
    batch = int(x.shape[0])
    analytic_flops = (
        analytic_flops_per_sample * batch
        if analytic_flops_per_sample is not None
        else None
    )
    # Prefer the documented analytic formula; XLA's cost model counts
    # transcendentals and rematerialized ops differently across versions.
    flops_per_step = analytic_flops if analytic_flops else xla_flops

    timed_step, timed_data = step, data
    if scan > 1:
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as _P

        from fluxmpi_tpu import config as _fm_config

        timed_step = make_train_step(
            loss_fn, optimizer, mesh=mesh, style="auto", remat=remat,
            scan_steps=scan,
        )
        timed_data = shard_batch(
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (scan, *a.shape)), (x, y)
            ),
            mesh, spec=_P(None, _fm_config.DP_AXIS_NAME),
        )
        if flops_per_step:
            flops_per_step *= scan

    rate, state = _steps_per_sec(
        timed_step, state, timed_data, warmup=3, steps=steps
    )
    # The discard itself is a signal (stderr alone was invisible to
    # trajectory tooling), so it rides the record as mfu_discarded.
    mfu, mfu_discarded = _discard_impossible(
        _raw_mfu(flops_per_step, rate, n_dev, device_kind)
    )

    value = round(batch * scan * rate * value_scale / n_dev, ndigits)
    anchor = _anchor_for(metric_name)
    result = {
        "metric": metric_name,
        "value": value,
        "unit": unit,
        "vs_baseline": round(value / anchor, 4) if anchor else 1.0,
        "platform": jax.default_backend(),
        "device_kind": device_kind,
        "n_chips": n_dev,
    }
    if mfu is not None:
        result["mfu"] = mfu
    if mfu_discarded:
        result["mfu_discarded"] = True
    if xla_flops and analytic_flops is None:
        result["flops_source"] = "xla_cost_analysis"
    if scan > 1:
        result["scan_steps"] = scan

    dispatch = _dispatch_probe(mesh)
    if dispatch is not None:
        result["dispatch"] = dispatch

    if loader_fed:
        fed = _loader_fed_rate(step=step, state=state, x=x, y=y,
                               mesh=mesh, n_dev=n_dev)
        if fed is not None:
            result["loader_fed_" + metric_name] = round(
                fed["per_chip"], ndigits
            )
            # Which loader path produced the number — a regression from a
            # silent device_gather→host fallback (e.g. the dataset
            # outgrowing the staging budget) must be attributable from
            # the record alone.
            result["loader_fed_path"] = fed["path"]
            if fed.get("assembly_samples_per_sec") is not None:
                # Assembly-only (loader iteration, no train step): the
                # third leg of the synthetic / loader-fed / assembly-only
                # breakdown, now ON the schema'd record instead of a
                # stderr line invisible to the trajectory.
                result["assembly_samples_per_sec"] = round(
                    fed["assembly_samples_per_sec"], 1
                )

    if fused_ab:
        ab = _fused_window_ab(
            loss_fn=loss_fn, optimizer=optimizer, host_params=host_params,
            mesh=mesh, n_dev=n_dev, x=x, y=y,
        )
        if ab is not None:
            # One-program flush windows (train_loop fuse="window") vs
            # the pipelined per-batch path over the SAME loader-fed
            # workload: throughput + dispatches-per-update per leg, so
            # the 1-dispatch-per-window claim is asserted in the record
            # rather than inferred.
            result["fused_window"] = ab
    return result


def _loader_fed_rate(*, step, state, x, y, mesh, n_dev) -> dict | None:
    """Re-time the same compiled step drawing batches through
    DistributedDataLoader — the device-gather fast path when the dataset
    qualifies (array-backed, fits the staging budget), the C++
    NativePrefetcher + per-batch transfer otherwise; either way the input
    pipeline is on the measured path. Returns ``{"per_chip": rate,
    "assembly_samples_per_sec": rate, "path": ...}`` so the
    synthetic/loader-fed/assembly-only breakdown lands on the schema'd
    record."""
    import jax

    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    try:
        batch = int(x.shape[0])
        # Enough host data for a few distinct batches without blowing host
        # RAM (ImageNet shapes: 1024 bf16 samples ≈ 300 MB).
        n_samples = min(max(batch * 4, 256), 1024)
        n_samples = max(n_samples, batch)  # at least one full batch
        host_x = np.asarray(x)
        host_y = np.asarray(y)
        reps = -(-n_samples // batch)
        host_x = np.concatenate([host_x] * reps, axis=0)[:n_samples]
        host_y = np.concatenate([host_y] * reps, axis=0)[:n_samples]
        dataset = ArrayDataset((host_x, host_y))
        # ONE loader for both measurements: its (mesh, axis) sharding and
        # any device-gather staging are built once and reused across
        # epochs — rebuilding per run would re-measure setup, not steady
        # state.
        loader = DistributedDataLoader(dataset, batch, mesh=mesh)
        gather_path = loader._use_device_gather(loader._array_backing())

        def run(n_steps: int, state):
            done = 0
            loss = None
            t0 = time.perf_counter()
            while done < n_steps:
                for data in loader:
                    state, loss = step(state, data)
                    done += 1
                    if done >= n_steps:
                        break
            _sync(loss)
            return n_steps / (time.perf_counter() - t0), state

        _, state = run(2, state)  # warmup: staging / prefetcher spin-up
        rate, state = run(8, state)
        out = {
            "per_chip": batch * rate / n_dev,
            "path": "device_gather" if gather_path else "host",
            "assembly_samples_per_sec": None,
        }

        # Assembly-only sub-rate so a gap vs synthetic is attributable in
        # ONE session: loader iteration with no train step — batch
        # production (device gather dispatch, or C++ gather + the
        # host→device transfers it initiates) drained per batch.
        try:
            t0 = time.perf_counter()
            n_loader = 0
            for _ in range(2):
                for data in loader:
                    jax.block_until_ready(data)
                    n_loader += 1
            out["assembly_samples_per_sec"] = (
                batch * n_loader / (time.perf_counter() - t0)
            )
        except Exception:
            pass
        return out
    except Exception as exc:  # pragma: no cover - diagnostics only
        print(f"bench: loader-fed path failed: {exc!r}", file=sys.stderr)
        return None


def _fused_window_ab(
    *, loss_fn, optimizer, host_params, mesh, n_dev, x, y
) -> dict | None:
    """A/B the one-program flush window (train_loop ``fuse="window"``:
    batch gather + the window's updates + metric reduction fused into
    one dispatch per window) against the pipelined per-batch path, on a
    loader-fed workload sized so the epoch is one window. Each leg
    reports per-chip throughput and — the directly-asserted claim —
    ``dispatches_per_update`` from the loop's own dispatch counter: 1.0
    pipelined, ``1/window`` fused."""
    import jax

    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
    from fluxmpi_tpu.parallel.train import replicate

    try:
        window = 8  # batches per epoch == updates per fused window
        lbs = 16
        gbs = lbs * n_dev
        n = gbs * window
        host_x = np.asarray(jax.device_get(x))
        host_y = np.asarray(jax.device_get(y))
        reps = -(-n // host_x.shape[0])
        host_x = np.concatenate([host_x] * reps, axis=0)[:n]
        host_y = np.concatenate([host_y] * reps, axis=0)[:n]
        dataset = ArrayDataset((host_x, host_y))
        step = make_train_step(loss_fn, optimizer, mesh=mesh)
        epochs = 2

        def run(fuse):
            loader = DistributedDataLoader(dataset, gbs, mesh=mesh)
            st = replicate(
                TrainState.create(host_params, optimizer, None), mesh
            )
            _, summary = train_loop(
                step, st, loader, epochs=epochs, fuse=fuse,
                flush_every=window, metrics=False,
            )
            return summary

        legs = {}
        for name, fuse in (("pipelined", False), ("fused", "window")):
            run(fuse)  # warmup: jit + the window's AOT compile (cached)
            s = run(fuse)
            legs[name] = {
                "samples_per_sec_per_chip": round(
                    s["examples_per_sec"] / n_dev, 1
                ),
                "dispatches_per_update": round(
                    s["dispatches"] / s["updates"], 4
                ),
            }
        if legs["fused"].get("dispatches_per_update", 1.0) >= 1.0:
            print("bench: fused A/B did not engage fusion", file=sys.stderr)
            return None
        pipelined_dpu = legs["pipelined"]["dispatches_per_update"]
        fused_dpu = legs["fused"]["dispatches_per_update"]
        return {
            "window": window,
            "pipelined": legs["pipelined"],
            "fused": legs["fused"],
            "dispatch_reduction": round(pipelined_dpu / fused_dpu, 2),
            "speedup": round(
                legs["fused"]["samples_per_sec_per_chip"]
                / legs["pipelined"]["samples_per_sec_per_chip"],
                3,
            ) if legs["pipelined"]["samples_per_sec_per_chip"] > 0 else None,
        }
    except Exception as exc:  # pragma: no cover - diagnostics only
        print(f"bench: fused A/B failed: {exc!r}", file=sys.stderr)
        return None


def _bn_loss(model):
    """Cross-entropy loss for BatchNorm-stateful image classifiers."""
    import jax.numpy as jnp
    import optax

    def loss_fn(p, mstate, b):
        bx, by = b
        logits, updates = model.apply(
            {"params": p, "batch_stats": mstate},
            bx,
            train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), by
        ).mean()
        return loss, updates["batch_stats"]

    return loss_fn


def _bench_resnet50():  # pragma: no cover - requires accelerator time
    import jax.numpy as jnp
    import optax

    def make(n_dev):
        from fluxmpi_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        # Per-chip batch (v5e sweep: 64 → 2510, 128 → 2714 img/s; see
        # FLUXMPI_TPU_RESNET_BATCH to re-sweep on other chips).
        per_chip = int(os.environ.get("FLUXMPI_TPU_RESNET_BATCH", "128"))
        batch = per_chip * n_dev
        x = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
        y = jnp.zeros((batch,), jnp.int32)
        return model, x, y, _bn_loss(model), optax.sgd(0.1, momentum=0.9)

    return _bench_workload(
        make_model_batch=make,
        stateful=True,
        metric_name="resnet50_images_per_sec_per_chip",
        unit="images/sec/chip",
        steps=30,
        ndigits=2,
        # ~4.09 GFLOPs fwd per 224² image; train step ≈ 3× fwd (fwd + 2× bwd).
        analytic_flops_per_sample=3 * 4.09e9,
        loader_fed=True,
    )


def _bench_cnn():
    import jax.numpy as jnp
    import optax

    def make(n_dev):
        from fluxmpi_tpu.models import CNN

        model = CNN(num_classes=10)
        batch = 256 * n_dev
        x = jnp.ones((batch, 32, 32, 3), jnp.float32)
        y = jnp.zeros((batch,), jnp.int32)
        return model, x, y, _bn_loss(model), optax.sgd(0.1, momentum=0.9)

    return _bench_workload(
        make_model_batch=make,
        stateful=True,
        metric_name="cifar_cnn_images_per_sec_per_chip",
        unit="images/sec/chip",
        steps=30,
        ndigits=1,
        loader_fed=True,
    )


def _bench_mlp():
    def make(n_dev):
        from fluxmpi_tpu.models import MLP

        # Per-chip batch; the scaling mode shrinks it (on a 1-core host, 8
        # virtual devices × 8192 samples serialize past XLA:CPU's 40 s
        # collective-rendezvous kill timer).
        per_chip = int(os.environ.get("FLUXMPI_TPU_BENCH_MLP_BATCH", "8192"))
        return _regression_workload(
            MLP(features=(256, 256, 256, 1)), per_chip, n_dev
        )

    return _bench_workload(
        make_model_batch=make,
        stateful=False,
        metric_name="mlp_quickstart_samples_per_sec_per_chip",
        unit="samples/sec/chip",
        steps=50,
        ndigits=1,
        # 4-layer MLP 1→256→256→256→1: 2·Σ(in·out) MACs... FLOPs = 2×,
        # train step ≈ 3× fwd.
        analytic_flops_per_sample=3 * 2 * (256 + 256 * 256 * 2 + 256),
        loader_fed=True,
        # The mlp step is small enough that per-dispatch host cost is a
        # measurable fraction of it; the steady-state default is the
        # pipelined multi-step path (8 updates per dispatch — measured
        # +35% single-chip, +19% at dp8 on the 2-core CPU smoke host).
        # FLUXMPI_TPU_BENCH_SCAN_STEPS=1 restores per-step dispatch for
        # A/B; rates and FLOPs account for the scan width either way.
        default_scan_steps=8,
        # One-program flush windows vs the pipelined loader-fed path —
        # the A/B rides the mlp child (and hence both scaling legs).
        fused_ab=True,
    )


def _regression_workload(model, per_chip_batch: int, n_dev: int):
    """Shared y=x² regression setup (quick-start parity task) used by the
    mlp and deq configs — one place for data/loss/optimizer policy."""
    import jax.numpy as jnp
    import optax

    batch = per_chip_batch * n_dev
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(-2, 2, size=(batch, 1)).astype(np.float32))
    y = x**2

    def loss_fn(p, mstate, b):
        bx, by = b
        return jnp.mean((model.apply(p, bx) - by) ** 2), mstate

    return model, x, y, loss_fn, optax.adam(1e-3)


def _parse_parallel_env() -> dict[str, int]:
    """FLUXMPI_TPU_BENCH_PARALLEL ("dp=4,fsdp=2") → ParallelConfig
    kwargs. Default: everything data-parallel (dp=-1, inferred). A
    malformed value warns and takes the default (the repo's env-typo
    convention: a typo degrades the leg, never crashes the child)."""
    spec = os.environ.get("FLUXMPI_TPU_BENCH_PARALLEL", "").strip()
    if not spec:
        return {"dp": -1}
    kwargs: dict[str, int] = {}
    try:
        for part in spec.split(","):
            axis, sep, size = part.partition("=")
            if not sep:
                raise ValueError(f"missing '=' in {part!r}")
            kwargs[axis.strip()] = int(size)
        # ParallelConfig is the single source of truth for axis names,
        # size bounds, and the one--1 rule: a spec it would reject in
        # the child degrades here instead, per the warn-and-default
        # contract. Keys are restricted to the plan AXES first —
        # non-axis constructor kwargs (fsdp_min_size=, strict=) are not
        # for this env var and would collide with _bench_train_loop's
        # own arguments.
        from fluxmpi_tpu.parallel.plan import _PLAN_AXES, ParallelConfig

        unknown = set(kwargs) - set(_PLAN_AXES)
        if unknown:
            raise ValueError(
                f"unknown axis {sorted(unknown)} (know {_PLAN_AXES})"
            )
        ParallelConfig(**kwargs)
    except (TypeError, ValueError) as exc:
        print(
            f"bench: ignoring FLUXMPI_TPU_BENCH_PARALLEL={spec!r} "
            f"({exc}); using dp=-1",
            file=sys.stderr,
        )
        return {"dp": -1}
    return kwargs


def _bench_train_loop():
    """Scaling-leg workload ON the real hot path: a small TransformerLM
    trained by ``train_loop(fuse="window")`` — one-program flush windows,
    device-gather loader, donated carries — under the ``ParallelConfig``
    named by ``FLUXMPI_TPU_BENCH_PARALLEL`` (default ``dp=-1``: all
    visible devices data-parallel). This is what the dp-scaling legs and
    the per-axis composition legs run (the pre-plan scaling legs timed a
    synthetic step; the number here is the driver users actually get).
    The record banks tokens/sec/chip plus a ``parallel`` block with the
    resolved axes, the plan's rule-hit counts, and the loop's own
    ``dispatches_per_update`` — the fused-path assertion
    (``1/window``) made under the plan-derived sharding."""
    import jax
    import jax.numpy as jnp
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
    from fluxmpi_tpu.parallel.train import replicate

    devs = _visible_devices()
    plan = ParallelConfig(**_parse_parallel_env(), fsdp_min_size=256).resolve(
        devs
    )
    mesh = fm.init(devices=devs, parallel=plan)
    n_dev = fm.total_workers()
    device_kind = devs[0].device_kind

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        vocab, seq = 8192, 256
        dims = dict(num_layers=4, d_model=512, num_heads=8, d_ff=2048)
        per_shard = 8
    else:
        vocab, seq = 256, 64
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128)
        per_shard = 8
    window = 8
    gbs = per_shard * plan.data_parallel_size
    model = TransformerLM(vocab_size=vocab, max_len=seq, **dims)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, size=(gbs * window, seq)).astype(np.int32)
    targets = rng.integers(0, vocab, size=(gbs * window, seq)).astype(np.int32)
    dataset = ArrayDataset((tokens, targets))
    optimizer = optax.adamw(1e-4)

    def loss_fn(p, mstate, batch):
        bx, by = batch
        logits = model.apply(p, bx, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), by
        ).mean()
        return loss, mstate

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    host_params = jax.device_get(params)

    def fresh_state():
        # The loop donates the state carry: every run needs its own.
        state = TrainState.create(host_params, optimizer)
        if plan.shards_parameters:
            state, _ = plan.shard_state(state)
        else:
            state = replicate(state, mesh)
        return state

    # The first state both places the layout and BANKS it on the plan —
    # the step factory then pins the same sharding the state carries.
    state0 = fresh_state()
    step = make_train_step(loss_fn, optimizer, parallel=plan)
    loader = DistributedDataLoader(dataset, gbs, mesh=mesh)

    def run(epochs, state):
        _, summary = train_loop(
            step, state, loader, epochs=epochs, fuse="window",
            flush_every=window, metrics=False,
        )
        return summary

    warm = run(1, state0)  # warmup: jit + the window's AOT compile (cached)
    epochs = max(2, int(os.environ.get("FLUXMPI_TPU_BENCH_STEPS", "24")) //
                 window)
    summary = run(epochs, fresh_state())
    value = round(summary["examples_per_sec"] * seq / n_dev, 1)
    sharded = 0
    if plan.state_sharding is not None:
        sharded = sum(
            1
            for sh in jax.tree_util.tree_leaves(plan.state_sharding.params)
            if hasattr(sh, "spec")
            and any(x is not None for x in tuple(sh.spec))
        )
    metric = "train_loop_tokens_per_sec_per_chip"
    anchor = _anchor_for(metric)
    desc = plan.describe()
    return {
        "metric": metric,
        "value": value,
        "unit": "tokens/sec/chip",
        "vs_baseline": round(value / anchor, 4) if anchor else 1.0,
        "platform": jax.default_backend(),
        "device_kind": device_kind,
        "n_chips": n_dev,
        "parallel": {
            "axes": desc["axes"],
            "data_parallel_size": desc["data_parallel_size"],
            "rule_hits": desc["rule_hits"],
            "sharded_param_leaves": sharded,
            "fused_window": summary["fused_window"],
            "dispatches_per_update": round(
                summary["dispatches"] / summary["updates"], 4
            ),
            "updates": summary["updates"],
            # The window AOT-compile cost lands in the warmup run; the
            # timed run must be a pure cache hit on the step's
            # (width, lbs, aval-fingerprint) window cache — recorded so
            # the per-leg saving is visible on the bench record.
            "compile_seconds": round(
                warm.get("window_compile_seconds") or 0.0, 3
            ),
            "window_cache": summary.get("window_cache"),
        },
    }


def _bench_autotune():
    """Layout-autotuner leg: ``init(parallel="auto")`` over the same
    TransformerLM workload the train_loop leg drives — the four-stage
    search (enumerate every dp×fsdp×tp factorization, prune on the
    static memory + AOT-cost models, fused-window trials for the
    survivors, bank the winner) end to end on the real machinery. The
    record's headline is the WINNER's fused-window throughput and the
    full ``fluxmpi_tpu.autotune/v1`` candidate table rides along under
    ``autotune`` (static scores + trial throughputs — the evidence the
    winner beat the hand-picked legs), validated by
    ``scripts/check_metrics_schema.py`` like every other contract."""
    import jax
    import jax.numpy as jnp
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.parallel.autotune import autotune

    devs = _visible_devices()
    fm.init(devices=devs, parallel="auto", compileplane=True)
    n_dev = len(devs)
    device_kind = devs[0].device_kind

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        vocab, seq = 8192, 256
        dims = dict(num_layers=4, d_model=512, num_heads=8, d_ff=2048)
        per_dev = 8
    else:
        vocab, seq = 256, 64
        dims = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128)
        per_dev = 8
    window = 8
    gbs = per_dev * n_dev
    model = TransformerLM(vocab_size=vocab, max_len=seq, **dims)
    rng = np.random.default_rng(0)
    batch = (
        rng.integers(0, vocab, size=(gbs, seq)).astype(np.int32),
        rng.integers(0, vocab, size=(gbs, seq)).astype(np.int32),
    )
    optimizer = optax.adamw(1e-4)

    def loss_fn(p, mstate, b):
        bx, by = b
        logits = model.apply(p, bx, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), by
        ).mean()
        return loss, mstate

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    res = autotune(
        loss_fn, optimizer, params, batch,
        devices=devs, window=window, trial_epochs=2,
        fsdp_min_size=256, seed=0, force=True,
    )
    winner = next(
        c for c in res.record["candidates"]
        if c["pruned"] is None and c["axes"] == res.record["winner"]["axes"]
    )
    eps = winner["trial"]["examples_per_sec"]
    value = round(eps * seq / n_dev, 1)
    metric = "autotune_tokens_per_sec_per_chip"
    anchor = _anchor_for(metric)
    return {
        "metric": metric,
        "value": value,
        "unit": "tokens/sec/chip",
        "vs_baseline": round(value / anchor, 4) if anchor else 1.0,
        "platform": jax.default_backend(),
        "device_kind": device_kind,
        "n_chips": n_dev,
        "autotune": res.record,
    }


def _bench_deq():
    """Deep Equilibrium model (BASELINE config 4): implicit fixed-point
    forward + custom-VJP implicit backward, per-chip samples/sec."""

    def make(n_dev):
        from fluxmpi_tpu.models import DEQ

        # Anderson acceleration: same fixed point as damped iteration
        # (oracle-tested) in ~1.6x fewer cell evaluations at this tol.
        return _regression_workload(
            DEQ(hidden=64, out=1, solver="anderson"), 2048, n_dev
        )

    return _bench_workload(
        make_model_batch=make,
        stateful=False,
        metric_name="deq_samples_per_sec_per_chip",
        unit="samples/sec/chip",
        steps=30,
        ndigits=1,
    )


def _bench_transformer():
    """GPT-style LM train step with the Pallas flash attention: the
    matmul-dense workload where MFU is meaningful (convnets at batch 128
    plateau far lower). tokens/sec/chip + MFU."""
    import jax
    import jax.numpy as jnp
    import optax

    on_tpu = jax.default_backend() == "tpu"
    vocab, seq = 32768, 1024
    if on_tpu:
        n_layers, d_model, n_heads, d_ff = 8, 1024, 16, 4096
        # Per-chip batch sweep knob (mirror of FLUXMPI_TPU_RESNET_BATCH).
        per_chip = int(os.environ.get("FLUXMPI_TPU_LM_BATCH", "8"))
    else:  # CPU smoke configuration
        n_layers, d_model, n_heads, d_ff, per_chip = 2, 128, 4, 256, 2

    def make(n_dev):
        from fluxmpi_tpu.models import TransformerLM
        from fluxmpi_tpu.ops import flash_attention_fn

        # Flash block-size re-tune knobs at this seq (the auto-pick tables
        # were tuned at 2048-8192; VERDICT r5 next #3).
        blk_q = os.environ.get("FLUXMPI_TPU_LM_BLOCK_Q")
        blk_k = os.environ.get("FLUXMPI_TPU_LM_BLOCK_K")
        model = TransformerLM(
            vocab_size=vocab, max_len=seq, num_layers=n_layers,
            d_model=d_model, num_heads=n_heads, d_ff=d_ff,
            dtype=jnp.bfloat16,
            attention_fn=flash_attention_fn(
                causal=True,
                block_q=int(blk_q) if blk_q else None,
                block_k=int(blk_k) if blk_k else None,
            ),
        )
        batch = per_chip * n_dev
        rng = np.random.default_rng(0)
        x = jnp.asarray(
            rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
        )
        y = jnp.asarray(
            rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
        )

        # Chunked fused unembed+CE head (ops/fused_ce.py): the [B·S, V]
        # logits tensor (0.5-1 GB at this config) is never materialized.
        # Default on; FLUXMPI_TPU_LM_FUSED_CE=0 restores the dense head
        # for A/B.
        fused_ce = os.environ.get("FLUXMPI_TPU_LM_FUSED_CE", "1") == "1"

        def loss_fn(p, mstate, b):
            bx, by = b
            if fused_ce:
                return model.apply(p, bx, train=True, targets=by).mean(), mstate
            logits = model.apply(p, bx, train=True)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), by
            ).mean()
            return loss, mstate

        return model, x, y, loss_fn, optax.adamw(1e-4)

    # 6·N_params FLOPs per trained token (fwd 2N + bwd 4N), the standard
    # decoder accounting. The embedding is weight-tied to the LM head
    # (models/transformer.py: embed.attend), so vocab·d counts ONCE — the
    # unembedding matmul; the input-side lookup is a gather, not FLOPs.
    # The attention term ~12·L·d·s adds <10% at seq 1024 and is left out
    # (slightly understating MFU rather than overstating it).
    n_params = 12 * n_layers * d_model**2 + vocab * d_model
    return _bench_workload(
        make_model_batch=make,
        stateful=False,
        metric_name="transformer_lm_tokens_per_sec_per_chip",
        unit="tokens/sec/chip",
        steps=20,
        ndigits=1,
        analytic_flops_per_sample=6 * n_params * seq,
        value_scale=seq,  # samples/sec → tokens/sec, inside the harness
    )


def _bench_unet():
    """DDPM UNet train step (epsilon-prediction MSE): the generative-vision
    workload — GroupNorm conv stages + spatial attention, conv-dominated
    like ResNet but without BatchNorm cross-batch state. images/sec/chip.
    Optional config: not in the headline fallback plan; run it via
    FLUXMPI_TPU_BENCH_CONFIG=unet."""
    import jax
    import jax.numpy as jnp
    import optax

    from fluxmpi_tpu.models import UNet, cosine_beta_schedule, ddpm_loss

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        side, base, mults, per_chip = 32, 128, (1, 2, 2, 4), 64
        attn_res = side // 4
    else:  # CPU smoke configuration
        side, base, mults, per_chip = 8, 8, (1, 2), 4
        # side//4 == 2 is never a reached resolution (sides are 8 and 4):
        # pin 4 so the stage-level attention blocks trace on CPU too, not
        # just the unconditional mid_attn.
        attn_res = 4

    holder = {}

    def make(n_dev):
        model = UNet(
            out_channels=3, base_channels=base, channel_mults=mults,
            blocks_per_stage=2, attn_resolutions=(attn_res,),
            groups=8 if base >= 32 else 4,
            dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        )
        batch = per_chip * n_dev
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(batch, side, side, 3)),
                        jnp.float32)
        y = jnp.zeros((batch,), jnp.int32)  # unused; harness shape slot
        betas = cosine_beta_schedule(1000)

        def loss_fn(p, mstate, b):
            bx, _ = b
            # Fixed rng: identical timestep/noise draws every step — the
            # compute being timed is constant across steps by design.
            return (
                ddpm_loss(model, p, bx, jax.random.PRNGKey(0), betas),
                mstate,
            )

        holder.update(model=model, x=x)
        return model, x, y, loss_fn, optax.adam(1e-4)

    return _bench_workload(
        make_model_batch=make,
        stateful=False,
        metric_name="unet_ddpm_images_per_sec_per_chip",
        unit="images/sec/chip",
        steps=20,
        ndigits=1,
        # No clean analytic formula for the UNet topology: use XLA's
        # compiled cost analysis (flops_source recorded in the output).
        init_fn=lambda: holder["model"].init(
            jax.random.PRNGKey(0), holder["x"][:2],
            jnp.zeros((2,), jnp.int32),
        ),
    )


def _bench_attention():
    """Flash (Pallas) vs XLA dense attention, fwd+bwd, bf16 — the "fast,
    not just correct" check on the one first-party kernel. Headline value is
    flash tokens/sec at the longest sequence; per-seq detail rides along."""
    import jax
    import jax.numpy as jnp

    from fluxmpi_tpu.ops import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    b, h, d = 4, 8, 64
    seqs = (2048, 4096, 8192) if on_tpu else (512,)
    detail = {}
    flash_rate = dense_rate = None

    def _dense(q, k, v):
        scale = 1.0 / np.sqrt(d)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        sq = q.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def _grad_step(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32))

        # One fused dispatch per step: grads AND the scalar sync probe live
        # in the same compiled program (separate host-side indexing ops
        # would each cost a dispatch).
        @jax.jit
        def g(q, k, v):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return dq[0, 0, 0, 0] + dk[0, 0, 0, 0] + dv[0, 0, 0, 0]

        def step(state, data):
            return state, g(*data)

        return step

    for seq in seqs:
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        shape = (b, seq, h, d)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        data = (q, k, v)

        flash_step = _grad_step(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
        )
        dense_step = _grad_step(_dense)
        steps = max(4, min(20, (1 << 22) // seq))
        try:
            flash_rate, _ = _steps_per_sec(flash_step, None, data, 2, steps)
        except Exception as exc:  # keep shorter-seq results on a long-seq OOM
            print(f"bench: flash attention failed at {seq}: {exc!r}",
                  file=sys.stderr)
            break
        try:
            dense_rate, _ = _steps_per_sec(dense_step, None, data, 2, steps)
        except Exception as exc:  # dense OOMs first at long seq
            print(f"bench: dense attention failed at {seq}: {exc!r}",
                  file=sys.stderr)
            dense_rate = None
        detail[str(seq)] = {
            "flash_tokens_per_sec": round(b * seq * flash_rate, 1),
            "dense_tokens_per_sec": (
                round(b * seq * dense_rate, 1) if dense_rate else None
            ),
            "flash_speedup": (
                round(flash_rate / dense_rate, 3) if dense_rate else None
            ),
        }

    if not detail:
        raise RuntimeError("no attention sequence length completed")
    seq = max(int(s) for s in detail)
    value = detail[str(seq)]["flash_tokens_per_sec"]
    result = {
        "metric": "flash_attention_tokens_per_sec",
        "value": value,
        "unit": f"tokens/sec (causal fwd+bwd, seq={seq}, bf16)",
        "vs_baseline": 1.0,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "per_seq": detail,
    }

    # Sliding window at the longest completed seq: the O(seq·window)
    # tile-skip's measured payoff (window = seq/16, e.g. 512 @ 8192).
    try:
        window = max(128, seq // 16)
        rng = jax.random.PRNGKey(1)
        kq, kk, kv = jax.random.split(rng, 3)
        shape = (b, seq, h, d)
        data = tuple(
            jax.random.normal(key, shape, jnp.bfloat16)
            for key in (kq, kk, kv)
        )
        win_step = _grad_step(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=window)
        )
        steps = max(4, min(20, (1 << 22) // seq))
        win_rate, _ = _steps_per_sec(win_step, None, data, 2, steps)
        result["windowed"] = {
            "window": window,
            "seq": seq,
            "flash_tokens_per_sec": round(b * seq * win_rate, 1),
            "speedup_vs_causal": (
                round(win_rate / flash_rate, 3) if flash_rate else None
            ),
        }
    except Exception as exc:  # pragma: no cover - diagnostics only
        print(f"bench: windowed attention failed: {exc!r}", file=sys.stderr)
    return result


def _bench_serving():
    """Serving plane A/B: static batching vs continuous batching on a
    mixed-length synthetic workload (forced-only config,
    ``FLUXMPI_TPU_BENCH_CONFIG=serving``; smoke-sized under
    ``FLUXMPI_TPU_BENCH_SMOKE=1`` — tier-1 runs it via
    tests/test_bench.py).

    Both legs run the SAME engine machinery (paged KV cache, prefill/
    decode split, one fixed-shape decode dispatch per iteration) — the
    only variable is the scheduling policy: static admits a new group
    only when every batch slot has drained (each group decodes at the
    pace of its LONGEST request), continuous refills slots the moment
    they free. The record banks per-leg token throughput, the speedup,
    and the steady-state retrace count across mid-flight joins (the
    zero-retrace claim, from the compile monitor)."""
    import jax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.serving import InferenceEngine
    from fluxmpi_tpu.telemetry import compileplane

    devs = _visible_devices()
    fm.init(devices=devs, compileplane=True)
    platform = devs[0].platform
    device_kind = devs[0].device_kind
    smoke = os.environ.get("FLUXMPI_TPU_BENCH_SMOKE") == "1"
    if smoke or platform == "cpu":
        dims = dict(vocab_size=64, max_len=128, num_layers=2, d_model=64,
                    num_heads=4, d_ff=128)
        slots, block, n_requests = 4, 8, 16
        long_new, short_new = 48, 6
    else:
        dims = dict(vocab_size=8192, max_len=512, num_layers=8,
                    d_model=512, num_heads=8, d_ff=2048)
        slots, block, n_requests = 8, 16, 64
        long_new, short_new = 192, 24
    import jax.numpy as jnp

    model = TransformerLM(**dims)
    rng = np.random.default_rng(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    # Mixed lengths: every slots-th request is long — exactly the shape
    # static batching is worst at (the whole gang waits for it).
    workload = []
    for i in range(n_requests):
        plen = int(rng.integers(4, 2 * block))
        max_new = long_new if i % slots == 0 else short_new
        workload.append(
            (rng.integers(0, dims["vocab_size"], size=(plen,)).astype(np.int32),
             max_new)
        )
    buckets = tuple(p.shape[0] for p, _ in workload)
    mon = compileplane.get_compile_monitor()

    legs = {}
    retraces = 0
    for name, continuous in (("static", False), ("continuous", True)):
        eng = InferenceEngine(
            model, params, slots=slots, block_size=block,
            max_queue=n_requests, continuous=continuous,
        )
        eng.warmup(prompt_lengths=buckets)
        mon.observe_flush()  # steady-state boundary for this leg
        for prompt, max_new in workload:
            eng.submit(prompt, max_new)
        summary = eng.run()
        info = mon.observe_flush()
        retraces += info["events"]
        legs[name] = {
            "tokens": summary["tokens"],
            "decode_steps": summary["decode_steps"],
            "wall_seconds": round(summary["wall_seconds"], 4),
            "tokens_per_sec": round(summary["tokens_per_sec"], 1),
        }
        eng.close()
    speedup = (
        round(legs["continuous"]["tokens_per_sec"]
              / legs["static"]["tokens_per_sec"], 3)
        if legs["static"]["tokens_per_sec"] else None
    )
    value = legs["continuous"]["tokens_per_sec"]
    metric = "serving_tokens_per_sec"
    anchor = _anchor_for(metric)
    return {
        "metric": metric,
        "value": value,
        "unit": "tokens/sec",
        "vs_baseline": round(value / anchor, 4) if anchor else 1.0,
        "platform": platform,
        "device_kind": device_kind,
        "n_chips": 1,
        "serving": {
            "requests": n_requests,
            "slots": slots,
            "block_size": block,
            "long_new": long_new,
            "short_new": short_new,
            "static": legs["static"],
            "continuous": legs["continuous"],
            "speedup": speedup,
            "steady_retraces": retraces,
        },
    }


def _compiled_memory_bytes(compiled) -> dict | None:
    """Per-program HBM footprint from XLA's static memory analysis — the
    per-leg attributable peak (the live ``peak_bytes_in_use`` gauge is a
    process-lifetime watermark, so an A/B's second leg could never read
    lower than its first). ``temp_bytes`` is where a dense attend's
    materialized ``[s, s]`` score tensors live; the flash kernel streams
    them through VMEM tiles instead."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for attr, key in (
        ("temp_size_in_bytes", "temp_bytes"),
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
    ):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            out[key] = float(v)
    return out or None


def _bench_attention_ab():
    """Kernel-plane A/B (ISSUE 19): ``attention="flash"`` vs ``"naive"``
    through the TransformerLM switch — same model, params, and data per
    leg; only the attention kernel differs. Both hot paths:

    - **training fwd+bwd**: AOT-compiled adamw step over the fused-CE
      loss — per-leg samples/sec + the compiled program's static HBM
      footprint (``memory_analysis``: the dense attend materializes
      ``[s, s]`` scores in temp space, flash streams tiles) + the
      steady-state retrace count (must be 0);
    - **paged serving decode**: ``InferenceEngine`` with continuous
      batching on a mixed-length workload — per-leg tokens/sec + the
      steady-state retrace count across mid-flight joins (0 = the
      no-retrace join contract survives the kernel swap).

    Forced/smoke config (``FLUXMPI_TPU_BENCH_CONFIG=attention_ab``). On
    CPU the flash legs run the Pallas kernels in interpret mode —
    correct but emulated, so the speedups are only meaningful on TPU;
    the retrace and memory accounting holds everywhere."""
    import jax
    import jax.numpy as jnp
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.serving import InferenceEngine
    from fluxmpi_tpu.telemetry import compileplane

    devs = _visible_devices()
    fm.init(devices=devs, compileplane=True)
    platform = devs[0].platform
    device_kind = devs[0].device_kind
    smoke = os.environ.get("FLUXMPI_TPU_BENCH_SMOKE") == "1"
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and not smoke:
        # Long-sequence config: where the dense attend's [s, s] scores
        # dominate temp memory and the flash claim is falsifiable.
        dims = dict(vocab_size=8192, max_len=2048, num_layers=4,
                    d_model=512, num_heads=8, d_ff=2048,
                    dtype=jnp.bfloat16)
        seq, batch, steps = 2048, 4, 10
        slots, block, n_requests = 4, 16, 12
        long_new, short_new = 64, 16
    else:  # CPU smoke: interpret-mode flash is slow, keep it tiny
        dims = dict(vocab_size=64, max_len=128, num_layers=2,
                    d_model=32, num_heads=4, d_ff=64)
        seq, batch, steps = 128, 2, 3
        slots, block, n_requests = 2, 8, 4
        long_new, short_new = 10, 4

    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.integers(0, dims["vocab_size"], size=(batch, seq)).astype(np.int32)
    )
    y = jnp.asarray(
        rng.integers(0, dims["vocab_size"], size=(batch, seq)).astype(np.int32)
    )
    opt = optax.adamw(1e-4)
    base = TransformerLM(**dims)
    params = base.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    opt_state = opt.init(params)
    mon = compileplane.get_compile_monitor()

    def train_leg(mode):
        model = base.clone(attention=mode)

        def step(p, s, bx, by):
            def loss_fn(q):
                return model.apply(q, bx, train=True, targets=by).mean()

            loss, grads = jax.value_and_grad(loss_fn)(p)
            updates, s2 = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s2, loss

        compiled = jax.jit(step).lower(params, opt_state, x, y).compile()
        mem = _compiled_memory_bytes(compiled)
        p, s, loss = compiled(params, opt_state, x, y)  # warmup call
        jax.block_until_ready(loss)
        mon.observe_flush()  # steady-state boundary for this leg
        t0 = time.perf_counter()
        for _ in range(steps):
            p, s, loss = compiled(p, s, x, y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        info = mon.observe_flush()
        leg = {
            "samples_per_sec": round(batch * steps / dt, 3),
            "tokens_per_sec": round(batch * seq * steps / dt, 1),
            "steady_retraces": info["events"],
        }
        if mem is not None:
            leg["compiled_hbm"] = mem
        return leg

    # One fixed mixed-length workload, shared by both decode legs.
    workload = []
    for i in range(n_requests):
        plen = int(rng.integers(4, 2 * block))
        max_new = long_new if i % slots == 0 else short_new
        workload.append(
            (rng.integers(0, dims["vocab_size"], size=(plen,)).astype(np.int32),
             max_new)
        )
    buckets = tuple(p.shape[0] for p, _ in workload)

    def decode_leg(mode):
        eng = InferenceEngine(
            base, params, slots=slots, block_size=block,
            max_queue=n_requests, continuous=True, attention=mode,
        )
        eng.warmup(prompt_lengths=buckets)
        mon.observe_flush()
        for prompt, max_new in workload:
            eng.submit(prompt, max_new)
        summary = eng.run()
        info = mon.observe_flush()
        eng.close()
        return {
            "tokens": summary["tokens"],
            "tokens_per_sec": round(summary["tokens_per_sec"], 1),
            "steady_retraces": info["events"],
        }

    train = {m: train_leg(m) for m in ("naive", "flash")}
    decode = {m: decode_leg(m) for m in ("naive", "flash")}

    def _speedup(legs, key):
        a = legs["flash"].get(key)
        b = legs["naive"].get(key)
        return round(a / b, 3) if a and b else None

    ab = {
        "seq": seq,
        "batch": batch,
        "steps": steps,
        "train": {**train,
                  "speedup": _speedup(train, "samples_per_sec")},
        "decode": {**decode,
                   "speedup": _speedup(decode, "tokens_per_sec")},
    }
    # The directly-asserted memory claim: flash's compiled temp space vs
    # the dense attend's, when the backend exposes memory_analysis.
    n_temp = (train["naive"].get("compiled_hbm") or {}).get("temp_bytes")
    f_temp = (train["flash"].get("compiled_hbm") or {}).get("temp_bytes")
    if n_temp is not None and f_temp is not None:
        ab["train"]["hbm_temp_saved_bytes"] = round(n_temp - f_temp, 1)

    value = train["flash"]["tokens_per_sec"]
    metric = "attention_ab_tokens_per_sec"
    anchor = _anchor_for(metric)
    return {
        "metric": metric,
        "value": value,
        "unit": "tokens/sec",
        "vs_baseline": round(value / anchor, 4) if anchor else 1.0,
        "platform": platform,
        "device_kind": device_kind,
        "n_chips": 1,
        "attention_ab": ab,
    }


_CHILD_FNS = {
    "resnet50": _bench_resnet50,
    "cnn": _bench_cnn,
    "mlp": _bench_mlp,
    "attention": _bench_attention,
    "attention_ab": _bench_attention_ab,
    "transformer": _bench_transformer,
    "deq": _bench_deq,
    "unet": _bench_unet,
    "serving": _bench_serving,
    "train_loop": _bench_train_loop,
    "autotune": _bench_autotune,
}


def _spawn(args: list[str], timeout: float, platform: str | None,
           extra_env: dict[str, str] | None = None):
    env = dict(os.environ)
    if platform is not None:
        env["FLUXMPI_TPU_BENCH_PLATFORM"] = platform
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )


def _parse_json_line(stdout: str) -> dict | None:
    for line in reversed(stdout.strip().splitlines()):
        try:
            result = json.loads(line)
            if isinstance(result, dict):
                return result
        except json.JSONDecodeError:
            continue
    return None


def _stderr_tail(proc) -> str:
    return " | ".join((proc.stderr or "").strip().splitlines()[-3:])


def _run_probe(timeout: float, platform: str | None) -> dict:
    """One plain check, in a child, of what JAX sees: backend init + one
    tiny matmul. Returns the record that lands under ``probe`` in the
    output JSON (``ok`` False with the reason when the child failed)."""
    record: dict = {
        "platform_variant": "env-default" if platform is None else platform,
        "timeout_s": timeout,
    }
    t0 = time.monotonic()
    try:
        proc = _spawn(["--probe"], timeout, platform)
    except subprocess.TimeoutExpired:
        record.update(ok=False, error=f"timed out after {timeout:.0f}s")
        return record
    record["elapsed_s"] = round(time.monotonic() - t0, 1)
    result = _parse_json_line(proc.stdout)
    if result and result.get("ok"):
        record.update(result)
    else:
        record.update(
            ok=False, exit=proc.returncode, error=_stderr_tail(proc)
        )
    return record


def _pin_child_platform() -> None:
    """Honor ``FLUXMPI_TPU_BENCH_PLATFORM`` in a child (the parent's pin,
    or the operator's on a direct ``--child`` run). Unset, jax's own
    ``JAX_PLATFORMS`` handling decides."""
    platform = os.environ.get("FLUXMPI_TPU_BENCH_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


def _probe_main() -> None:
    _pin_child_platform()
    import jax

    devices = jax.devices()
    import jax.numpy as jnp

    x = jnp.ones((128, 128), jnp.bfloat16)
    np.asarray(jax.device_get(x @ x))
    print(
        json.dumps(
            {
                "ok": True,
                "platform": jax.default_backend(),
                "device_kind": devices[0].device_kind,
                "n_devices": len(devices),
            }
        ),
        flush=True,
    )


def _run_child(
    config: str,
    timeout: float,
    platform: str | None,
    extra_env: dict[str, str] | None = None,
) -> dict | None:
    """Run one bench config in a child process; parse its final JSON line.
    Returns None on timeout/crash/garbage so the caller can fall back."""
    trace_dir = os.environ.get("FLUXMPI_TPU_BENCH_TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        # The same config can run as multiple children (headline run +
        # the dp1/dpN scaling pair): discriminate the filename by the
        # device count so the scaling comparison's traces both survive.
        devs = (extra_env or {}).get(
            "FLUXMPI_TPU_BENCH_DEVICES",
            os.environ.get("FLUXMPI_TPU_BENCH_DEVICES", ""),
        )
        tag = f"{config}.dp{devs}" if devs else config
        extra_env = {
            **(extra_env or {}),
            "FLUXMPI_TPU_TRACE": os.path.join(
                trace_dir, f"trace.{tag}.json"
            ),
        }
    try:
        proc = _spawn(["--child", config], timeout, platform, extra_env)
    except subprocess.TimeoutExpired:
        print(f"bench: {config} timed out after {timeout:.0f}s", file=sys.stderr)
        return None
    result = _parse_json_line(proc.stdout)
    if result and "metric" in result:
        return result
    print(
        f"bench: {config} produced no metric (exit {proc.returncode}): "
        + _stderr_tail(proc),
        file=sys.stderr,
    )
    return None


def _child_main(config: str) -> None:
    _pin_child_platform()
    _enable_compilation_cache()
    result = _CHILD_FNS[config]()
    # Export the span ring if FLUXMPI_TPU_TRACE named a path (set by the
    # parent's FLUXMPI_TPU_BENCH_TRACE_DIR passthrough, or directly):
    # the workload ran under fm.init, which wired tracing from the env.
    from fluxmpi_tpu.telemetry import tracing as _tracing

    _tracing.shutdown()
    print(json.dumps(result), flush=True)


def _scaling_efficiency(per_chip_1: float, per_chip_n: float) -> float:
    """DP scaling efficiency: per-chip throughput at dp=N as a fraction of
    per-chip throughput at dp=1 (1.0 = perfect linear scaling)."""
    if per_chip_1 <= 0:
        return 0.0
    return round(per_chip_n / per_chip_1, 4)


def _run_scaling(
    remaining_s: float,
    accel_probe: dict | None,
    accel_platform: str | None = None,
) -> dict | None:
    """DP scaling-efficiency measurement: the mlp workload at dp=1 vs dp=N,
    same per-chip batch (weak scaling). On a multi-chip accelerator this
    runs on the chips (submesh via FLUXMPI_TPU_BENCH_DEVICES), using the
    platform variant the probe succeeded with; on a single-chip or dead
    accelerator it runs on an 8-virtual-device CPU mesh — efficiency
    numbers there prove the plumbing, not the ICI."""
    n_accel = (accel_probe or {}).get("n_devices", 0)
    if accel_probe and n_accel > 1:
        platform, n, extra = accel_platform, n_accel, {}
        # Stable label for external tooling; the real backend ("tpu" on a
        # pod slice) rides in a separate "backend" key so the scaling
        # number is never mistaken for the cpu-virtual plumbing proof.
        mode = "accelerator"
        backend = accel_probe.get("platform")
    else:
        platform, n = "cpu", 8
        backend = "cpu"
        extra = _cpu_virtual_env()
        mode = "cpu-virtual"
    # Workload: the BASELINE scaling target is ResNet-50 DP ≥70% on a pod
    # slice, so that is the default on real multi-chip TPU; elsewhere the
    # legs run the train_loop child — the REAL fused hot path
    # (train_loop(fuse="window") under a plan-derived sharding), retiring
    # the synthetic-step scaling measurement. See docs/performance.md
    # "Pod-slice scaling runbook" / "Choosing a layout".
    cfg = os.environ.get("FLUXMPI_TPU_BENCH_SCALING_CONFIG") or (
        "resnet50" if backend == "tpu" else "train_loop"
    )
    cap = 600.0 if cfg == "resnet50" else 240.0
    per_child = min(cap, (remaining_s - 10) / 2)
    if per_child < 45:
        return None
    # Pin the plan spec per leg (dp=-1: all the leg's devices) — an
    # operator-set FLUXMPI_TPU_BENCH_PARALLEL is for the forced
    # train_loop child and must not leak into the dp1 leg (dp=4 on one
    # device is a TopologyMismatchError that would silently drop the
    # whole scaling block).
    extra = {**extra, "FLUXMPI_TPU_BENCH_MLP_BATCH": "512",
             "FLUXMPI_TPU_BENCH_PARALLEL": ""}
    r1 = _run_child(cfg, per_child, platform,
                    {**extra, "FLUXMPI_TPU_BENCH_DEVICES": "1"})
    rn = _run_child(cfg, per_child, platform,
                    {**extra, "FLUXMPI_TPU_BENCH_DEVICES": str(n)})
    if not (r1 and rn):
        return None
    return {
        "mode": mode,
        "backend": backend,
        "config": cfg,
        "n_chips": rn.get("n_chips", n),
        "per_chip_at_dp1": r1["value"],
        "per_chip_at_dpN": rn["value"],
        "scaling_efficiency": _scaling_efficiency(r1["value"], rn["value"]),
        # Per-n_dev attribution: where the efficiency goes — compiled
        # step (synthetic), input pipeline (loader_fed / assembly), or
        # dispatch floor. Keys mirror the child records they come from.
        "breakdown": {
            "dp1": _leg_breakdown(r1),
            "dpN": _leg_breakdown(rn),
        },
    }


def _leg_breakdown(rec: dict) -> dict:
    """Lift one scaling child's diagnostic sub-rates into the scaling
    block (synthetic vs loader-fed vs assembly-only vs dispatch floor)."""
    out: dict = {"synthetic": rec.get("value")}
    for key, val in rec.items():
        if key.startswith("loader_fed_") and key != "loader_fed_path":
            out["loader_fed"] = val
    if rec.get("loader_fed_path") is not None:
        out["loader_path"] = rec["loader_fed_path"]
    if rec.get("assembly_samples_per_sec") is not None:
        out["assembly"] = rec["assembly_samples_per_sec"]
    dispatch = rec.get("dispatch")
    if isinstance(dispatch, dict):
        out["dispatch_us"] = dispatch.get("per_dispatch_us")
    if "scan_steps" in rec:
        out["scan_steps"] = rec["scan_steps"]
    par = rec.get("parallel")
    if isinstance(par, dict):
        # train_loop-child legs: the real driver's own dispatch
        # accounting under the plan-derived sharding.
        out["dispatches_per_update"] = par.get("dispatches_per_update")
        out["window"] = par.get("fused_window")
    attn_ab = rec.get("attention_ab")
    if isinstance(attn_ab, dict):
        # The kernel-plane A/B's headline ratios, lifted next to the
        # fused-window ones so one breakdown block carries both
        # dispatch- and kernel-level attribution.
        out["attention_ab"] = {
            "train_speedup": (attn_ab.get("train") or {}).get("speedup"),
            "decode_speedup": (attn_ab.get("decode") or {}).get("speedup"),
            "hbm_temp_saved_bytes": (attn_ab.get("train") or {}).get(
                "hbm_temp_saved_bytes"
            ),
        }
    fused = rec.get("fused_window")
    if isinstance(fused, dict):
        # The fused-vs-pipelined dispatch accounting per leg: how many
        # host dispatches one optimizer update costs on each path, and
        # the reduction factor the one-program window buys.
        out["fused_window"] = {
            "window": fused.get("window"),
            "pipelined_dispatches_per_update": (fused.get("pipelined") or {})
            .get("dispatches_per_update"),
            "fused_dispatches_per_update": (fused.get("fused") or {})
            .get("dispatches_per_update"),
            "dispatch_reduction": fused.get("dispatch_reduction"),
            "speedup": fused.get("speedup"),
        }
    return out


def _cpu_virtual_env() -> dict[str, str]:
    """Child env for the 8-virtual-device CPU mesh (append, not clobber
    — the operator's own XLA_FLAGS survive; for duplicated flags the
    last occurrence wins in XLA's parser)."""
    flags = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    return {"XLA_FLAGS": flags}


# The per-axis composition legs: same train_loop(fuse="window") workload,
# same 8 devices, different ParallelConfig — what each axis costs/buys
# relative to pure dp (docs/performance.md, "Choosing a layout").
_AXIS_LEGS: tuple[tuple[str, str], ...] = (
    ("dp", "dp=8"),
    ("dp_fsdp", "dp=4,fsdp=2"),
    ("dp_tp", "dp=4,tp=2"),
)


def _axis_leg_summary(rec: dict) -> dict:
    par = rec.get("parallel") or {}
    return {
        "axes": par.get("axes"),
        "per_chip": rec.get("value"),
        "unit": rec.get("unit"),
        "n_chips": rec.get("n_chips"),
        "data_parallel_size": par.get("data_parallel_size"),
        "dispatches_per_update": par.get("dispatches_per_update"),
        "sharded_param_leaves": par.get("sharded_param_leaves"),
        "rule_hits": par.get("rule_hits"),
        "compile_seconds": par.get("compile_seconds"),
        "window_cache": par.get("window_cache"),
    }


def _run_axis_bench(remaining_s: float) -> dict | None:
    """Per-axis bench children on the CPU virtual mesh: dp-only vs
    dp×fsdp vs dp×tp, every leg through the real
    ``train_loop(fuse="window")`` driver under its ``ParallelConfig``.
    Returns ``{leg: summary}`` for the legs that completed (None when
    none did / no budget)."""
    per_child = min(240.0, (remaining_s - 10) / len(_AXIS_LEGS))
    if per_child < 45:
        return None
    out: dict[str, dict] = {}
    for name, spec in _AXIS_LEGS:
        # Pin DEVICES too: these legs need all 8 virtual devices — an
        # operator-set submesh truncation (a TPU-run knob) would make
        # every fixed-size plan a TopologyMismatchError.
        rec = _run_child(
            "train_loop",
            per_child,
            "cpu",
            {**_cpu_virtual_env(), "FLUXMPI_TPU_BENCH_PARALLEL": spec,
             "FLUXMPI_TPU_BENCH_DEVICES": ""},
        )
        if rec is not None:
            out[name] = _axis_leg_summary(rec)
    return out or None


def _bench_result_key(bench: dict) -> tuple:
    """Identity of a bench configuration inside the shared JSONL stream:
    re-running the same config REPLACES its line instead of appending a
    duplicate, so an interrupted sweep accumulates one line per config
    across restarts (restart-proof result banking, VERDICT r5 top-next)."""
    return (
        bench.get("metric"),
        # Failure records carry no device_kind/n_chips — the config name
        # keeps failures from different benches on distinct lines.
        bench.get("config"),
        bench.get("platform"),
        bench.get("device_kind"),
        bench.get("n_chips"),
        bench.get("scan_steps"),
        bench.get("smoke"),
    )


def _merge_bench_jsonl(path: str, record: dict) -> None:
    """Merge one flush record into the JSONL file keyed by bench config:
    non-bench lines and other configs are preserved verbatim, the
    matching config's line is replaced, new configs append. Written
    tmp-then-rename so a crash mid-merge never truncates banked results
    (the checkpoint commit discipline, docs/fault_tolerance.md).
    All writers sharing one JSONL serialize on the ``<path>.lock``
    sidecar (:func:`fluxmpi_tpu.telemetry.sinks.jsonl_lock` — the
    per-line sink appenders take the same lock), so the
    read-merge-replace never drops a line another writer lands
    mid-merge. Note the replace swaps the inode: follow with ``tail
    -F`` (not ``-f``)."""
    from fluxmpi_tpu.telemetry.sinks import jsonl_lock

    with jsonl_lock(path):
        _merge_bench_jsonl_locked(path, record)


def _merge_bench_jsonl_locked(path: str, record: dict) -> None:
    key = _bench_result_key(record["bench"])
    lines: list[str] = []
    if os.path.exists(path):
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.rstrip("\n")
                if not line.strip():
                    continue
                try:
                    old = json.loads(line)
                except json.JSONDecodeError:
                    lines.append(line)  # never drop someone else's data
                    continue
                if (
                    isinstance(old, dict)
                    and isinstance(old.get("bench"), dict)
                    and _bench_result_key(old["bench"]) == key
                ):
                    continue  # superseded by this run
                lines.append(line)
    lines.append(json.dumps(record))
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _emit_telemetry(result: dict) -> None:
    """Mirror the headline result through the telemetry record layer (one
    JSONL line, fluxmpi_tpu.telemetry schema) when FLUXMPI_TPU_BENCH_JSONL
    is set. The stdout JSON contract is untouched — this is the same
    record shape riding the same pipe every other metric in the system
    uses, so one tail/validator covers training runs and bench runs
    alike. Lines are MERGED keyed by config (see _bench_result_key), not
    appended: an interrupted sweep re-run banks each config once."""
    path = os.environ.get("FLUXMPI_TPU_BENCH_JSONL")
    if not path:
        return
    try:
        from fluxmpi_tpu.telemetry import MetricsRegistry

        reg = MetricsRegistry()
        labels = {
            k: str(result[k])
            for k in ("platform", "device_kind")
            if k in result
        }
        reg.gauge("bench." + result["metric"], **labels).set(
            float(result["value"])
        )
        if "mfu" in result:
            reg.gauge("bench.mfu", **labels).set(float(result["mfu"]))
        scaling = result.get("scaling")
        if isinstance(scaling, dict) and "scaling_efficiency" in scaling:
            reg.gauge("bench.scaling_efficiency", **labels).set(
                float(scaling["scaling_efficiency"])
            )
        # The full result rides along so the JSONL line alone reconstructs
        # the run (validated as a bench record by check_metrics_schema).
        record = reg.flush(bench=result)
        reg.close(flush=False)
        _merge_bench_jsonl(path, record)
    except Exception as exc:  # emission must never sink the bench run
        print(f"bench: telemetry emit failed: {exc!r}", file=sys.stderr)


def _run_smoke(remaining) -> None:
    """Smoke mode: the full bench contract — child spawn, JSON shape,
    schema, dispatch probe, loader-fed breakdown, (optionally) the
    scaling pair — in well under a minute on CPU, no TPU check. This
    is what tier-1 CI runs (tests/test_bench.py) so bench/schema
    breakage is caught before a round, not during one.
    ``FLUXMPI_TPU_BENCH_SMOKE_SCALING=0`` skips the scaling pair (the
    tier-1 test does, for suite-budget reasons; the slow-marked variant
    covers it)."""
    os.environ.setdefault("FLUXMPI_TPU_BENCH_STEPS", "6")
    os.environ.setdefault("FLUXMPI_TPU_BENCH_MLP_BATCH", "256")
    # A forced config rides smoke mode too (the serving A/B's tier-1
    # entry point: FLUXMPI_TPU_BENCH_SMOKE=1 + _CONFIG=serving); the
    # scaling pair only applies to the default mlp smoke.
    config = os.environ.get("FLUXMPI_TPU_BENCH_CONFIG") or "mlp"
    # The train_loop/autotune children compose axes over the
    # 8-virtual-device mesh; a bare smoke host may expose only one CPU
    # device.
    extra = (
        _cpu_virtual_env() if config in ("train_loop", "autotune") else None
    )
    result = _run_child(config, 240.0, "cpu", extra) or _failed(config, "cpu")
    # Marked on failures too: a CI smoke crash must never read as a real
    # benchmark round in the shared JSONL trajectory.
    result["smoke"] = 1
    if config == "mlp" and os.environ.get(
        "FLUXMPI_TPU_BENCH_SMOKE_SCALING", "1"
    ) == "1":
        scaling = _run_scaling(min(remaining(), 340.0), None, None)
        if scaling is not None:
            result["scaling"] = scaling
        # Fast dp×fsdp composition leg: the plan-derived sharding on the
        # real fused driver, smoke-sized (skippable via the same
        # FLUXMPI_TPU_BENCH_SMOKE_SCALING=0 knob as the pair above).
        leg_budget = min(remaining() - 10, 180.0)
        leg = (
            _run_child(
                "train_loop",
                leg_budget,
                "cpu",
                {**_cpu_virtual_env(),
                 "FLUXMPI_TPU_BENCH_PARALLEL": "dp=4,fsdp=2",
                 "FLUXMPI_TPU_BENCH_DEVICES": ""},
            )
            if leg_budget >= 45
            else None
        )
        if leg is not None:
            result["parallel_axes"] = {"dp_fsdp": _axis_leg_summary(leg)}
    _report(result)


def _failed(config: str, platform: str | None, **extra) -> dict:
    """The record of a run that produced no metric. The failed config
    (and attempted platform) ride it: they are part of the JSONL merge
    key, so failures from different configs bank as distinct lines
    instead of silently replacing each other."""
    return {"metric": "bench_failed", "value": 0.0, "unit": "none",
            "vs_baseline": 0.0, "config": config,
            **({"platform": platform} if platform else {}), **extra}


def _report(result: dict) -> None:
    """Bank and print the run's one result line. A run that produced no
    metric exits non-zero — a failure never reads as a finished round."""
    _emit_telemetry(result)
    print(json.dumps(result))
    if result["metric"] == "bench_failed":
        raise SystemExit(1)


def main() -> None:
    t_start = time.monotonic()
    budget = float(
        os.environ.get("FLUXMPI_TPU_BENCH_BUDGET", str(_DEFAULT_BUDGET_S))
    )

    def remaining() -> float:
        return budget - (time.monotonic() - t_start)

    if os.environ.get("FLUXMPI_TPU_BENCH_SMOKE") == "1":
        _run_smoke(remaining)
        return

    forced = os.environ.get("FLUXMPI_TPU_BENCH_CONFIG")
    if forced and forced not in _CHILD_FNS:
        raise SystemExit(
            f"FLUXMPI_TPU_BENCH_CONFIG={forced!r} unknown; "
            f"pick one of {tuple(_CHILD_FNS)}"
        )
    platform = os.environ.get("FLUXMPI_TPU_BENCH_PLATFORM") or None
    timeout_override = os.environ.get("FLUXMPI_TPU_BENCH_TIMEOUT")

    # Phase 1: which devices. An explicit CPU request runs on the CPU;
    # anything else must find a TPU — one child checks, since this
    # parent stays off jax — or the run ends here, non-zero, with no
    # CPU number under the same harness.
    cpu_requested = "cpu" in (platform, os.environ.get("JAX_PLATFORMS"))
    probe = None
    if not cpu_requested:
        probe = _run_probe(min(_PROBE_TIMEOUT_S, remaining()), platform)
        if probe.get("platform") != "tpu":
            print(f"bench: no TPU: {probe}", file=sys.stderr)
            _report(
                _failed(
                    forced or "probe", platform, probe={"attempts": [probe]}
                )
            )
    accel_ok = not cpu_requested  # past this point: a TPU, or a CPU run
    child_platform = platform if accel_ok else "cpu"

    if forced:
        # unet is forced-only (not in the plan) but is as compile-heavy
        # as resnet50 on a cold cache: same 900 s.
        child_to = float(timeout_override) if timeout_override else {
            **dict(_CONFIGS), "unet": 900.0, "train_loop": 240.0,
            "autotune": 300.0,
        }.get(forced, 300.0)
        # The train_loop/autotune children compose axes — on a CPU
        # target a bare host may expose one device, so give them the
        # 8-virtual-device mesh (same treatment as the smoke path; a
        # TPU target keeps its real devices).
        extra = (
            _cpu_virtual_env()
            if forced in ("train_loop", "autotune") and not accel_ok
            else None
        )
        _report(
            _run_child(forced, child_to, child_platform, extra)
            or _failed(forced, child_platform)
        )
        return

    plan = list(_CONFIGS) if accel_ok else [("mlp", 150.0), ("cnn", 300.0)]

    # Phase 2: the headline — the first config of the plan that yields
    # a metric.
    result = None
    for config, child_to in plan:
        if timeout_override:
            child_to = float(timeout_override)
        child_to = min(child_to, remaining() - 20)
        if child_to < 45:
            print(f"bench: budget exhausted before {config}", file=sys.stderr)
            break
        result = _run_child(config, child_to, child_platform)
        if result is not None:
            break

    if result is None:
        # `config` is the last plan entry attempted — names which bench
        # the failure line belongs to in the JSONL bank.
        result = _failed(config, child_platform)
    result["probe"] = {"attempts": [probe] if probe else []}

    # Phase 3: secondary metrics, budget permitting — never at the expense
    # of the primary line.
    if accel_ok and remaining() > 300 and result["metric"] != "bench_failed":
        attn = _run_child(
            "attention", min(360.0, remaining() - 60), child_platform
        )
        if attn is not None:
            result["attention"] = {
                k: attn[k] for k in ("value", "unit", "per_seq")
                if k in attn
            }
    # The LM child also runs on an explicit CPU run (cheap there).
    if remaining() > 420 and result["metric"] != "bench_failed":
        lm = _run_child(
            "transformer", min(480.0, remaining() - 60), child_platform
        )
        if lm is not None:
            result["transformer_lm"] = {
                k: lm[k] for k in ("value", "unit", "mfu", "vs_baseline")
                if k in lm
            }
    # Kernel-plane A/B (flash vs naive through the model switch, both
    # hot paths) — runs on an explicit CPU run too: the retrace and
    # compiled-memory accounting is meaningful there even though the
    # interpret-mode flash timings are not.
    if remaining() > 300 and result["metric"] != "bench_failed":
        ab = _run_child(
            "attention_ab", min(360.0, remaining() - 60), child_platform
        )
        if ab is not None and "attention_ab" in ab:
            result["attention_ab"] = ab["attention_ab"]
    if accel_ok and remaining() > 200 and result["metric"] != "bench_failed":
        deq = _run_child("deq", min(240.0, remaining() - 60), child_platform)
        if deq is not None:
            result["deq"] = {
                k: deq[k] for k in ("value", "unit") if k in deq
            }
    if remaining() > 120 and result["metric"] != "bench_failed":
        scaling = _run_scaling(remaining(), probe, platform)
        if scaling is not None:
            result["scaling"] = scaling
    if remaining() > 150 and result["metric"] != "bench_failed":
        # Per-axis composition legs (dp vs dp×fsdp vs dp×tp) on the CPU
        # virtual mesh — the plan-composition proof, every leg on the
        # real fused train_loop driver.
        axes = _run_axis_bench(remaining())
        if axes is not None:
            result["parallel_axes"] = axes
    if remaining() > 150 and result["metric"] != "bench_failed":
        # Layout autotuner over the same CPU virtual mesh: the full
        # enumerate→prune→trial→bank record banks next to the per-axis
        # legs so the winner can be audited against the hand-picked
        # layouts above.
        at_rec = _run_child(
            "autotune", min(300.0, remaining() - 30), "cpu",
            _cpu_virtual_env(),
        )
        if at_rec is not None and "autotune" in at_rec:
            result["autotune"] = at_rec["autotune"]

    _report(result)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--probe":
        _probe_main()
    elif len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child_main(sys.argv[2])
    else:
        main()
