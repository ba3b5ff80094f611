"""Driver ``train``: a training cell through the program's own entry
points: ``fm.init`` -> ``DistributedDataLoader`` -> ``make_train_step``
-> ``train_loop``.

Set-up builds ONE object, the compiled step with its state, drives it
from the seeded weights through its first updates (the cell's
``check_steps``, through the window's own call and feed) and hands that
same object to the measured window: one call of ``train_loop(...,
steps=N)`` timed on the host clock from just before the call to its
return, the device drained. The plain reference follows the same first
updates once the window has closed and the program's state is freed.
"""

from __future__ import annotations

import os
import time

import numpy as np

from harness import compare, device as device_mod, manifest, trace as trace_mod


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _timed_loader_class():
    from fluxmpi_tpu.data import DistributedDataLoader

    class TimedLoader(DistributedDataLoader):
        """The harness's own timer around the loader it hands to
        ``train_loop`` (traced runs only): seconds the loop waited for
        a batch, or for an epoch's device-side bring-up."""

        wait_s = 0.0

        def __iter__(self):
            it = super().__iter__()
            while True:
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                finally:
                    self.wait_s += time.perf_counter() - t0
                yield batch

        def device_epoch(self):
            t0 = time.perf_counter()
            try:
                return super().device_epoch()
            finally:
                self.wait_s += time.perf_counter() - t0

    return TimedLoader


def _norm_reader(cell):
    """One jitted function: a parameter-shaped tree of the program ->
    per-leaf norms in the reference's naming."""
    import jax

    cfg, prog, ref = cell.config, cell.program, cell.reference

    @jax.jit
    def norms(variables, model_state):
        return ref.leaf_norms(prog.from_program(variables, model_state, cfg))

    return norms


def run(cell, *, seed: int, seconds: float, trace: bool, phases,
        control: str | None = None, broken: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.data import (
        ArrayDataset,
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
    from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor

    spec, cfg, prog = cell.spec, cell.config, cell.program
    devices = jax.devices()[: cell.chips]
    parallel = spec.get("parallel")
    fm.init(devices=devices, compileplane=True,
            parallel=ParallelConfig(**parallel) if parallel else None)
    plan = fm.global_plan()
    mesh = plan.mesh if plan is not None else fm.global_mesh()
    phases.mark("init")

    # ---- the system under test, from the seed --------------------------
    data = spec["data"]
    rows_per_step = data["rows_per_step"]
    arrays = prog.make_dataset(cfg, data, seed)
    loader_cls = _timed_loader_class() if trace else DistributedDataLoader
    loader = loader_cls(
        DistributedDataContainer(ArrayDataset(arrays)), rows_per_step,
        **spec.get("loader", {}),
    )
    model = prog.build_model(cfg, spec.get("attention", "flash"))
    optimizer = prog.make_optimizer(spec["optimizer"])
    key = seed_key(seed)

    def make_state(k):
        variables, model_state = prog.to_program(
            cell.reference.make_weights(cfg, k), cfg
        )
        return TrainState.create(variables, optimizer, model_state)

    sharded = plan is not None and plan.shards_parameters
    if sharded:
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(plan.mesh, s),
            plan.partition_specs(jax.eval_shape(make_state, key)),
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        shardings = NamedSharding(mesh, P())
    state = jax.jit(make_state, out_shardings=shardings)(key)
    if sharded:
        state = plan.shard_state(state)[0]
    step = make_train_step(prog.make_loss(model), optimizer,
                           **({"parallel": plan} if plan is not None else {}))
    jax.block_until_ready(state)
    phases.mark("weights_and_data")

    # ---- first updates: through the window's own call and feed --------
    flush_every = spec["loop"]["flush_every"]
    check_steps = list(spec["check_steps"])
    norms = _norm_reader(cell)
    records: list[dict] = []
    program: dict = {"losses": {}}
    done = 0
    for i, n in enumerate(check_steps):
        if done:
            loader.load_state_dict(
                {"epoch": 0, "cursor": done, "seed": loader.seed}
            )
        if broken == "state_unchanged":
            # The test's broken timed path: a step that returns its
            # state as it got it.
            _, summary = train_loop(
                step, jax.tree_util.tree_map(jnp.copy, state), loader,
                steps=n, flush_every=flush_every, metrics=records.append,
            )
        else:
            state, summary = train_loop(
                step, state, loader, steps=n, flush_every=flush_every,
                metrics=records.append,
            )
        done += n
        program["losses"][f"step{done}"] = summary["loss"]
        if summary["fused_window"]:
            rec = records[-1]
            program["losses"][f"mean{done}"] = rec["loss_window_mean"]
            program["losses"][f"max{done}"] = rec["loss_window_max"]
        if i == 0:
            program["grad_state_norms"] = jax.device_get(norms(
                prog.grad_state(state.opt_state), None
            ))
            phases.mark("compile_or_cache_load")
    # The seeded weights again (made anew, not kept: a copy held through
    # the first updates would sit on top of their activations).
    start = jax.jit(
        lambda k: (lambda s: (s.params, s.model_state))(make_state(k)),
        out_shardings=(shardings.params, shardings.model_state)
        if sharded else shardings,
    )(key)
    delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x - y, a, b))((state.params, state.model_state), start)
    program["delta_norms"] = jax.device_get(norms(*delta))
    del start, delta
    fused = summary["fused_window"]
    unit = fused or 1

    # ---- size the window from a short timed call, then measure --------
    probe = max(unit, (spec["loop"]["probe_steps"] // unit) * unit)
    t0 = time.perf_counter()
    state, _ = train_loop(step, state, loader, steps=probe,
                          flush_every=flush_every)
    per_step = (time.perf_counter() - t0) / probe
    n_steps = max(unit, int(round(seconds / per_step / unit)) * unit)
    mon = get_compile_monitor()
    phases.mark("warm_up")

    setup_s = phases.since_start()
    events0 = mon.events
    if trace:
        loader.wait_s = 0.0
    t0 = time.perf_counter()
    state, summary = train_loop(step, state, loader, steps=n_steps,
                                flush_every=flush_every)
    elapsed = time.perf_counter() - t0
    compiles = mon.events - events0
    items = summary["examples"] * prog.items_per_row(data)
    values = {
        "train_items_per_s_chip": items / elapsed / cell.chips,
        "setup_s": setup_s,
        "compiles_in_window": float(compiles),
        "dispatches_per_update": summary["dispatches"] / summary["updates"],
        "window_elapsed_s": elapsed,
        "window_updates": summary["updates"],
        "fused_window": fused or 0,
    }
    if trace:
        values["input_wait_pct"] = 100.0 * loader.wait_s / elapsed

    # ---- a short traced call of the same object ------------------------
    reduced = None
    if trace:
        logdir = os.path.join(manifest.ROOT, ".bench_out", "trace", cell.name)
        traced_steps = max(unit, int(
            round(spec["loop"]["trace_seconds"] / per_step / unit)) * unit)
        trace_mod.start(logdir, spec["loop"].get("host_tracer_level", 1))
        t0 = time.perf_counter()
        state, _ = train_loop(step, state, loader, steps=traced_steps,
                              flush_every=flush_every)
        traced_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        reduced = trace_mod.reduce(trace_mod.load_events(logdir), traced_s)
        values["traced_updates"] = traced_steps

    device = device_mod.report(devices)
    values["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9

    # ---- free the program's state, then the reference ------------------
    del state, step, loader
    fm.shutdown()
    jax.clear_caches()
    batches = [
        tuple(a[i * rows_per_step:(i + 1) * rows_per_step] for a in arrays)
        for i in range(done)
    ]
    t_ref = time.perf_counter()
    comparison = _compare(cell, key, batches, check_steps[0], program,
                          precision="f32", devices=devices)
    out = {
        "values": values, "device": device, "trace": reduced,
        "comparison": comparison, "attempted": int(summary["updates"]),
        "failed": 0, "reference_s": time.perf_counter() - t_ref,
        "cell": cell,
    }
    if control is not None:
        out["control"] = _compare(cell, key, batches, check_steps[0], None,
                                  precision=control, devices=devices)
    return out



def _compare(cell, key, batches, grad_state_after, program, *, precision,
             devices) -> compare.Comparison:
    """The reference's readings at float32 against the program's (or,
    for the control, against the reference's own at ``precision``)."""
    spec = cell.spec
    ref_args = dict(
        grad_state_after=grad_state_after, optimizer=spec["optimizer"],
        rows_per_block=spec["reference"].get("rows_per_block"),
        shard=_sharder(devices) if len(devices) > 1 else None,
    )
    want = cell.reference.train_readings(
        cell.config, key, batches, precision="f32", **ref_args
    )
    if program is None:
        low = cell.reference.train_readings(
            cell.config, key, batches, precision=precision, **ref_args
        )
        done = len(batches)
        program = {
            "losses": {f"step{done}": low["losses"][-1]},
            "grad_state_norms": low["grad_state_norms"],
            "delta_norms": low["delta_norms"],
        }
        if grad_state_after != done:
            program["losses"][f"step{grad_state_after}"] = (
                low["losses"][grad_state_after - 1]
            )
    limits = spec["limits"]
    losses = want["losses"]
    gaps = []
    for name, got in program["losses"].items():
        kind, upto = name.rstrip("0123456789"), int(
            name[len(name.rstrip("0123456789")):])
        ref = {"step": losses[upto - 1], "mean": float(np.mean(losses[:upto])),
               "max": float(np.max(losses[:upto]))}[kind]
        gaps.append(abs(float(got) - ref))
    out = compare.Comparison()
    out.add("loss_gap", max(gaps), limits["loss_gap"])
    # Which leaf speaks for a number is the cell's own choice, set from
    # readings (PERF.md): the widest gap, or the median leaf's where one
    # small leaf's rounding noise decides the widest.
    leaf_gap = {"worst": compare.worst_leaf_gap,
                "median": compare.median_leaf_gap}
    out.add("grad_norm_gap", leaf_gap[spec["compare"]["grad_norm_gap"]](
        program["grad_state_norms"], want["grad_state_norms"]),
        limits["grad_norm_gap"])
    out.add("update_norm_gap", leaf_gap[spec["compare"]["update_norm_gap"]](
        program["delta_norms"], want["delta_norms"]),
        limits["update_norm_gap"])
    for what in ("grad_state_norms", "delta_norms"):
        print(f"widest {what} ({precision}):",
              compare.worst_leaves(program[what], want[what]),
              compare.gap_quantiles(program[what], want[what]), flush=True)
    return out


def _sharder(devices):
    """Shapes -> a layout of the reference's arrays over the cell's chips
    (last axis, where it divides), so that a model one chip cannot hold
    fits; XLA partitions the plain ``jax.numpy`` program by itself."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("ref",))
    n = len(devices)

    def layout(x):
        if x.ndim and x.shape[-1] % n == 0:
            return NamedSharding(mesh, P(*([None] * (x.ndim - 1)), "ref"))
        return NamedSharding(mesh, P())

    return lambda shapes: jax.tree_util.tree_map(layout, shapes)
