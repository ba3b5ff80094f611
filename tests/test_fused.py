"""One-program flush windows (train_loop fuse="window"): fused-vs-
pipelined bit-exactness (final state AND summary metrics, including the
scan_steps path and a mid-epoch kill-and-resume landing inside a
window), auto-enable/forced-raise resolution, window-boundary flush
metrics + preemption, AOT compile attribution on the device/run-health
planes, the zero-cost-when-off contract on the fused path, and the
device-gather budget env hardening."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import fluxmpi_tpu as fm
from fluxmpi_tpu import faults
from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
from fluxmpi_tpu.errors import FaultInjectedError
from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
from fluxmpi_tpu.parallel.train import make_window_program, replicate
from fluxmpi_tpu.telemetry import (
    AnomalyDetector,
    CompileMonitor,
    GoodputTracker,
    MetricsRegistry,
    anomaly,
    compileplane,
    goodput,
)
from fluxmpi_tpu.utils import CheckpointManager


@pytest.fixture(autouse=True)
def _clean_flags():
    faults.clear()
    fm.clear_preemption()
    yield
    faults.clear()
    fm.clear_preemption()


@pytest.fixture()
def planes_off():
    """Run-health + device planes guaranteed off around a test."""
    prev_tracker = goodput.set_goodput_tracker(GoodputTracker(enabled=False))
    prev_detector = anomaly.set_anomaly_detector(None)
    prev_monitor = compileplane.set_compile_monitor(None)
    try:
        yield
    finally:
        goodput.set_goodput_tracker(prev_tracker)
        anomaly.set_anomaly_detector(prev_detector)
        compileplane.set_compile_monitor(prev_monitor)


def _pieces(n=256, features=(16, 16, 1)):
    from fluxmpi_tpu.models import MLP

    model = MLP(features=features)

    def loss_fn(p, ms, b):
        bx, by = b
        return jnp.mean((model.apply(p, bx) - by) ** 2), ms

    opt = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    x = rng.uniform(-2, 2, size=(n, 1)).astype(np.float32)
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1)))
    )
    return loss_fn, opt, params, ArrayDataset((x, x**2))


def _fresh(params, opt, world):
    return replicate(TrainState.create(params, opt, None), world)


def _leaves_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y))
        ),
        a, b,
    )


def _loader(ds, world, **kw):
    kw.setdefault("shuffle", True)
    kw.setdefault("seed", 11)
    return DistributedDataLoader(ds, 64, mesh=world, **kw)


# ---------------------------------------------------------------------------
# Equivalence: the fused window must not change the math.
# ---------------------------------------------------------------------------


def test_fused_bit_identical_to_pipelined_and_scan(world):
    # Same batches, same update sequence -> bit-identical final state
    # across the per-batch pipelined path, the scan_steps multi-step
    # path, and the fused window; summary metrics match the per-batch
    # path exactly (loss is the last update's on both).
    loss_fn, opt, params, ds = _pieces()

    step = make_train_step(loss_fn, opt, mesh=world)
    s_pipe, sum_pipe = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse=False,
    )

    step_k = make_train_step(loss_fn, opt, mesh=world, scan_steps=2)
    s_scan, sum_scan = train_loop(
        step_k, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse=False,
    )

    s_fused, sum_fused = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse="window",
    )

    _leaves_equal(s_pipe.params, s_fused.params)
    _leaves_equal(s_pipe.opt_state, s_fused.opt_state)
    _leaves_equal(s_scan.params, s_fused.params)
    for key in ("updates", "epochs", "examples", "loss"):
        assert sum_fused[key] == sum_pipe[key]
        if key != "loss":  # scan summary loss means over the last group
            assert sum_fused[key] == sum_scan[key]
    # The host-cost contract: one dispatch per window (flush_every=50
    # clamps to the 4-batch epoch -> one window per pass) vs one per
    # batch on the pipelined path.
    assert sum_fused["fused_window"] == 4
    assert sum_fused["dispatches"] == 2
    assert sum_pipe["dispatches"] == 8


def test_fused_scan_steps_step_is_subsumed(world):
    # A step built with scan_steps=K still fuses (the window does its
    # own scan over the banked single-update body) and stays
    # bit-identical to its own pipelined multi-step run.
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world, scan_steps=2)
    s_pipe, _ = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse=False,
    )
    s_fused, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse="window", flush_every=2,
    )
    _leaves_equal(s_pipe.params, s_fused.params)
    assert summary["fused_window"] == 2
    assert summary["dispatches"] == 4  # 2 windows x 2 epochs


# ---------------------------------------------------------------------------
# Resolution: auto-enable, clamping, forced failures.
# ---------------------------------------------------------------------------


def test_fuse_auto_engages_on_device_gather_loader(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1
    )
    assert summary["fused_window"] == 4  # flush_every=50 clamped to epoch
    assert summary["dispatches"] == 1


def test_fuse_auto_falls_back_on_host_path(world):
    # A transform forces the host loader path: auto quietly keeps the
    # pipelined driver instead of failing.
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    loader = _loader(ds, world, transform=lambda b: b, device_gather=False)
    _, summary = train_loop(
        step, _fresh(params, opt, world), loader, epochs=1
    )
    assert summary["fused_window"] is None
    assert summary["dispatches"] == 4


def test_fuse_auto_falls_back_on_indivisible_flush_every(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1,
        flush_every=3,  # 4-batch epoch % 3 != 0
    )
    assert summary["fused_window"] is None


def test_fuse_auto_keeps_exact_steps_budget(world):
    # Window dispatch rounds a steps budget up to whole windows; AUTO
    # must never silently change what `steps` means, so a misaligned
    # budget keeps the pipelined path (forcing fuse="window" opts into
    # the documented rounding).
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), steps=10
    )
    assert summary["updates"] == 10
    assert summary["fused_window"] is None
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), steps=8
    )
    assert summary["updates"] == 8
    assert summary["fused_window"] == 4


def test_fuse_window_forced_raises_naming_the_reason(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    with pytest.raises(ValueError, match="not a DistributedDataLoader"):
        train_loop(step, _fresh(params, opt, world),
                   iter(list(_loader(ds, world))), steps=2, fuse="window")
    with pytest.raises(ValueError, match="device-gather"):
        train_loop(
            step, _fresh(params, opt, world),
            _loader(ds, world, transform=lambda b: b, device_gather=False),
            epochs=1, fuse="window",
        )
    with pytest.raises(ValueError, match="divide"):
        train_loop(step, _fresh(params, opt, world), _loader(ds, world),
                   epochs=1, fuse="window", flush_every=3)
    with pytest.raises(ValueError, match="fuse must be"):
        train_loop(step, _fresh(params, opt, world), _loader(ds, world),
                   epochs=1, fuse="sideways")


def test_fuse_window_forced_rejects_shard_map_steps(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world, style="shard_map")
    with pytest.raises(ValueError, match="metadata"):
        train_loop(step, _fresh(params, opt, world), _loader(ds, world),
                   epochs=1, fuse="window")


def test_make_window_program_validates(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    with pytest.raises(ValueError, match="width"):
        make_window_program(step, width=0, lbs=8)
    with pytest.raises(ValueError, match="style='auto'"):
        make_window_program(lambda s, b: (s, 0.0), width=2, lbs=8)


# ---------------------------------------------------------------------------
# Window-boundary instrumentation and budgets.
# ---------------------------------------------------------------------------


def test_fused_flush_metrics_at_window_granularity(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    reg = MetricsRegistry()
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=3,
        flush_every=2, metrics=reg,
    )
    assert summary["updates"] == 12
    assert summary["fused_window"] == 2
    assert reg.counter("train.steps").value == 12
    assert reg.counter("train.examples").value == 12 * 64
    # Every window is a flush: 6 windows -> 6 interval observations.
    assert reg.histogram("train.step_seconds").count == 6
    assert reg.gauge("train.window.size").value == 2.0
    assert reg.counter("train.window.dispatches").value == 6
    assert reg.gauge("train.loss").value == pytest.approx(summary["loss"])


def test_fused_instrumented_step_reports_grad_norm(world):
    loss_fn, opt, params, ds = _pieces()
    reg = MetricsRegistry()
    step = make_train_step(loss_fn, opt, mesh=world, metrics=True)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1,
        metrics=reg,
    )
    assert summary["fused_window"] == 4
    assert reg.gauge("train.grad_norm").value > 0.0


def test_fused_hook_receives_window_stats(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    records = []
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        metrics=records.append,
    )
    assert sum(r["steps"] for r in records) == summary["updates"]
    for r in records:
        # The scan carry's on-device interval reduction, surfaced.
        assert r["loss_window_max"] >= r["loss"]
        assert r["loss_window_mean"] > 0


def test_fused_steps_budget_rounds_up_to_windows(world):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), steps=5,
        fuse="window", flush_every=4,
    )
    # Whole windows only: 5 updates round up to 2 windows = 8.
    assert summary["updates"] == 8
    assert summary["dispatches"] == 2


def test_fused_window_program_cache_survives_runs(world):
    # A second train_loop over the same step must reuse the AOT
    # executable, not re-lower it (the compile-once contract).
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    train_loop(step, _fresh(params, opt, world), _loader(ds, world),
               epochs=1)
    hot = step.__fluxmpi_compiled__ if hasattr(
        step, "__fluxmpi_compiled__") else step
    cache = getattr(hot, "__fluxmpi_window_cache__")
    assert len(cache) == 1
    (key,) = cache
    assert key[:2] == (4, 64)  # (width, lbs, state/data/perm avals...)
    first = cache[key]
    train_loop(step, _fresh(params, opt, world), _loader(ds, world),
               epochs=1)
    assert cache[key] is first and len(cache) == 1


def test_fused_window_cache_keys_on_dataset_avals(world):
    # Reusing one step across differently-sized datasets must compile a
    # fresh window program, not dispatch run 1's executable against run
    # 2's staged arrays (AOT executables check nothing at call time).
    loss_fn, opt, params, ds_small = _pieces(n=256)
    _, _, _, ds_big = _pieces(n=512)
    step = make_train_step(loss_fn, opt, mesh=world)
    _, s1 = train_loop(step, _fresh(params, opt, world),
                       _loader(ds_small, world), epochs=1, fuse="window",
                       flush_every=4)
    _, s2 = train_loop(step, _fresh(params, opt, world),
                       _loader(ds_big, world), epochs=1, fuse="window",
                       flush_every=4)
    assert s1["fused_window"] == s2["fused_window"] == 4
    assert s2["updates"] == 8  # 512 samples / gbs 64 = 8 batches
    hot = getattr(step, "__fluxmpi_compiled__", step)
    assert len(hot.__fluxmpi_window_cache__) == 2


# ---------------------------------------------------------------------------
# Fault tolerance: resume (mid-window included) and preemption.
# ---------------------------------------------------------------------------


def test_fused_kill_and_resume_bit_identical(world, tmp_path):
    # Crash a PIPELINED run mid-epoch (its checkpoint cursor lands at a
    # window-unaligned batch), resume FUSED: the first window is short
    # (realigning the flush grid), and the final state is bit-identical
    # to the uninterrupted reference.
    loss_fn, opt, params, ds = _pieces()

    def fresh():
        return _fresh(params, opt, world)

    step = make_train_step(loss_fn, opt, mesh=world)
    state_ref, sum_ref = train_loop(
        step, fresh(), _loader(ds, world), steps=8, fuse=False
    )

    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    step2 = make_train_step(loss_fn, opt, mesh=world)
    with faults.scope("data.fetch@step=6"):
        with pytest.raises(FaultInjectedError):
            train_loop(step2, fresh(), _loader(ds, world), steps=8,
                       fuse=False, checkpoint=mgr, save_every=3)
    banked = mgr.latest_step()
    assert banked == 3  # mid-epoch, NOT aligned to the 4-batch window

    mgr2 = CheckpointManager(str(tmp_path / "run"), async_save=False)
    step3 = make_train_step(loss_fn, opt, mesh=world)
    state_res, summary = train_loop(
        step3, fresh(), _loader(ds, world), steps=8, fuse="window",
        flush_every=4, checkpoint=mgr2, resume=True,
    )
    assert summary["resumed_from"] == banked
    assert summary["updates"] == 8
    assert summary["fused_window"] == 4
    # Cursor 3 lands inside epoch 0's window: one short 1-update window
    # realigns the grid, then epoch 1 runs as one full window.
    assert summary["dispatches"] == 2
    _leaves_equal(state_res.params, state_ref.params)
    _leaves_equal(state_res.opt_state, state_ref.opt_state)
    assert summary["loss"] == sum_ref["loss"]


def test_fused_save_and_resume_fused_both_sides(world, tmp_path):
    # Fused run interrupted by its steps budget, resumed fused: saves
    # land at window boundaries and the concatenated run matches the
    # uninterrupted one exactly.
    loss_fn, opt, params, ds = _pieces()

    def fresh():
        return _fresh(params, opt, world)

    step = make_train_step(loss_fn, opt, mesh=world)
    state_ref, _ = train_loop(
        step, fresh(), _loader(ds, world), epochs=3, fuse="window",
        flush_every=2,
    )
    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    train_loop(step, fresh(), _loader(ds, world), steps=6, fuse="window",
               flush_every=2, checkpoint=mgr, save_every=2)
    state_res, summary = train_loop(
        step, fresh(), _loader(ds, world), epochs=3, fuse="window",
        flush_every=2, checkpoint=mgr, resume=True,
    )
    assert summary["resumed_from"] == 6
    assert summary["updates"] == 12
    assert summary["epochs"] == 3
    _leaves_equal(state_res.params, state_ref.params)


def test_fused_preemption_drains_at_window_boundary(world, tmp_path):
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    fm.request_preemption()
    state, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse="window", flush_every=2, checkpoint=mgr,
    )
    # The flag is honored at the first window boundary: exactly one
    # window ran, the emergency checkpoint banked it.
    assert summary["preempted"] is True
    assert summary["updates"] == 2
    assert mgr.latest_step() == 2
    fm.clear_preemption()
    state_res, summary2 = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse="window", flush_every=2, checkpoint=mgr, resume=True,
    )
    assert summary2["updates"] == 8
    state_ref, _ = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        fuse="window", flush_every=2,
    )
    _leaves_equal(state_res.params, state_ref.params)


# ---------------------------------------------------------------------------
# Device/run-health planes on the fused path.
# ---------------------------------------------------------------------------


def test_fused_aot_compile_attributed(world, planes_off):
    # The AOT-lowered window program has no jit cache to poll: the
    # monitor's executable-handle path must still attribute it —
    # compile.function_seconds{train_loop.window} and the aot counters
    # appear, and warmup compiles never read as steady-state retraces.
    mon = CompileMonitor()
    compileplane.set_compile_monitor(mon)
    reg = MetricsRegistry()
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        flush_every=2, metrics=reg,
    )
    assert summary["fused_window"] == 2
    assert (
        reg.counter(
            "compile.aot_programs", function="train_loop.window"
        ).value == 1
    )
    assert (
        reg.counter(
            "compile.aot_seconds", function="train_loop.window"
        ).value > 0
    )
    assert (
        reg.counter(
            "compile.function_seconds", function="train_loop.window"
        ).value > 0
    )
    # One warmup compile, zero steady-state retraces.
    assert mon.retraces == []
    assert (
        reg.counter(
            "compile.retraces", function="train_loop.window"
        ).value == 0
    )


def test_fuse_auto_falls_back_when_elastic_remap_breaks_budget(world,
                                                               tmp_path):
    # Same-geometry resumes keep updates ≡ cursor (mod window); an
    # ELASTIC remap (different global batch size) rescales the cursor
    # while updates stays, so window boundaries would straddle — and
    # overshoot — an aligned steps budget. AUTO must fall back to the
    # pipelined path and stop exactly at the budget.
    loss_fn, opt, params, ds = _pieces()

    def fresh():
        return _fresh(params, opt, world)

    step = make_train_step(loss_fn, opt, mesh=world)
    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    # gbs 64 (4 batches/epoch): bank updates=2 at cursor=2.
    train_loop(step, fresh(), _loader(ds, world), steps=2, fuse=False,
               checkpoint=mgr, save_every=2)
    # Resume with gbs 32 (8 batches/epoch): cursor remaps 2 -> 4 while
    # updates stays 2 — updates ≢ cursor (mod 4). Fused windows would
    # land at updates 6, 10: past steps=8.
    loader = DistributedDataLoader(ds, 32, mesh=world, shuffle=True,
                                   seed=11)
    mgr2 = CheckpointManager(str(tmp_path / "run"), async_save=False)
    _, summary = train_loop(step, fresh(), loader, steps=8,
                            flush_every=4, checkpoint=mgr2, resume=True)
    assert summary["resumed_from"] == 2
    assert summary["fused_window"] is None  # auto fell back
    assert summary["updates"] == 8  # budget hit EXACTLY


def test_fused_mid_window_resume_is_not_a_retrace(world, tmp_path,
                                                  planes_off):
    # A mid-window resume compiles TWO widths (the short realignment
    # window + the full one). Both must land inside warmup: the full
    # program is pre-built before the short window's flush marks the
    # run steady, so a legitimate resume never fires
    # steady_state_retrace (or burns the once-per-run auto-profile).
    loss_fn, opt, params, ds = _pieces()

    def fresh():
        return _fresh(params, opt, world)

    step = make_train_step(loss_fn, opt, mesh=world)
    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.scope("data.fetch@step=6"):
        with pytest.raises(FaultInjectedError):
            train_loop(step, fresh(), _loader(ds, world), steps=8,
                       fuse=False, checkpoint=mgr, save_every=3)
    assert mgr.latest_step() == 3  # window-unaligned cursor

    mon = CompileMonitor()
    compileplane.set_compile_monitor(mon)
    reg = MetricsRegistry()
    step2 = make_train_step(loss_fn, opt, mesh=world)
    mgr2 = CheckpointManager(str(tmp_path / "run"), async_save=False)
    _, summary = train_loop(
        step2, fresh(), _loader(ds, world), steps=8, fuse="window",
        flush_every=4, checkpoint=mgr2, resume=True, metrics=reg,
    )
    assert summary["dispatches"] == 2  # short 1-update window + full 4
    assert mon.retraces == []
    assert (
        reg.counter(
            "compile.retraces", function="train_loop.window"
        ).value == 0
    )
    assert (
        reg.counter(
            "compile.aot_programs", function="train_loop.window"
        ).value == 2
    )


def test_compile_monitor_aot_retrace_after_steady():
    # Unit-level: an AOT compile AFTER the warmup boundary reads as a
    # steady-state retrace naming the program.
    mon = CompileMonitor()
    reg = MetricsRegistry()
    mon.track_aot("train_loop.window")
    mon.note_aot_compile("train_loop.window", 0.5)
    info = mon.observe_flush(reg)  # warmup boundary
    assert info["steady"] is False
    mon.note_aot_compile("train_loop.window", 0.25)
    mon._note_duration(
        "/jax/core/compile/backend_compile_duration", 0.25
    )
    info = mon.observe_flush(reg)
    assert info["steady"] is True
    assert info["functions"] == ["train_loop.window"]
    assert (
        reg.counter(
            "compile.aot_programs", function="train_loop.window"
        ).value == 2
    )
    assert reg.counter(
        "compile.aot_seconds", function="train_loop.window"
    ).value == pytest.approx(0.75)
    assert (
        reg.counter(
            "compile.retraces", function="train_loop.window"
        ).value == 1
    )


def test_fused_goodput_books_aot_compile(world, planes_off):
    tracker = GoodputTracker()
    goodput.set_goodput_tracker(tracker)
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    # Fresh step object -> fresh AOT cache -> the compile is paid (and
    # booked) inside this run.
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        flush_every=2,
    )
    rep = summary["goodput"]
    assert summary["fused_window"] == 2
    assert rep["buckets"]["compile"] > 0
    assert rep["buckets"]["step"] > 0
    assert rep["updates"] == 8
    # FLOPs came from the window executable's cost model.
    assert rep["flops_per_update"] and rep["flops_per_update"] > 0


def test_fused_mfu_survives_window_cache_hit(world, planes_off):
    # reset_run() clears the per-run FLOPs at every train_loop start; a
    # second fused run that cache-hits the banked window executable must
    # still re-derive them (MFU would otherwise silently vanish from
    # run 2 while the pipelined path keeps reporting it).
    tracker = GoodputTracker()
    goodput.set_goodput_tracker(tracker)
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, s1 = train_loop(step, _fresh(params, opt, world),
                       _loader(ds, world), epochs=1, flush_every=2)
    assert s1["fused_window"] == 2
    assert s1["goodput"]["flops_per_update"]
    _, s2 = train_loop(step, _fresh(params, opt, world),
                       _loader(ds, world), epochs=1, flush_every=2)
    hot = getattr(step, "__fluxmpi_compiled__", step)
    assert len(hot.__fluxmpi_window_cache__) == 1  # run 2 cache-hit
    assert s2["goodput"]["flops_per_update"] == s1["goodput"][
        "flops_per_update"
    ]


def test_fuse_auto_falls_back_on_ragged_scan_epoch(world):
    # A scan_steps step on an epoch its stacking adapter would truncate:
    # the pipelined path drops the ragged trailing scan group (4 updates
    # from 5 batches at k=2); fusing would train all 5 — AUTO must not
    # silently change what an epoch means, so it keeps the pipelined
    # path (forcing fuse="window" opts into the whole-epoch behavior).
    loss_fn, opt, params, ds = _pieces(n=320)  # 5 batches at gbs=64
    step = make_train_step(loss_fn, opt, mesh=world, scan_steps=2)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1
    )
    assert summary["fused_window"] is None
    assert summary["updates"] == 4  # (5 // 2) * 2: ragged group dropped
    _, forced = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1,
        fuse="window", flush_every=5,
    )
    assert forced["fused_window"] == 5
    assert forced["updates"] == 5  # explicit opt-in trains the whole epoch


def test_fuse_auto_falls_back_on_scan_misaligned_steps(world):
    # steps window-aligned but NOT scan-aligned: pipelined scan groups
    # round the budget UP (steps=6 at k=4 -> 8 updates); fusing would
    # stop at 6 — a silent budget-semantics change AUTO must refuse.
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world, scan_steps=4)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), steps=6,
        flush_every=2,
    )
    assert summary["fused_window"] is None
    assert summary["updates"] == 8  # scan quantization, as before
    # A scan-aligned budget fuses fine.
    _, aligned = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), steps=8,
        flush_every=2,
    )
    assert aligned["fused_window"] == 2
    assert aligned["updates"] == 8


def test_fused_ticks_watchdog_per_window(world):
    from fluxmpi_tpu.telemetry import watchdog

    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    before = watchdog._progress_value()
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=2,
        flush_every=2,
    )
    assert summary["dispatches"] == 4
    # One liveness tick per window dispatch PLUS the flush's
    # interval-updates tick — the stall detector is never blind for
    # more than one window.
    assert watchdog._progress_value() >= before + 4 + summary["updates"]


def test_fused_fully_off_costs_nothing(world, planes_off, monkeypatch):
    # The monkeypatch-explode contract extended to the fused path: with
    # every plane off, one fused run performs no tracker clock reads,
    # segments, compile-monitor calls, or AOT notes.
    tracker = goodput.get_goodput_tracker()
    assert not tracker.enabled
    assert compileplane.get_compile_monitor() is None

    def boom(*a, **k):
        raise AssertionError("plane touched on the fused off path")

    tracker._clock = boom
    tracker.segment = boom
    tracker.add = boom
    tracker.note_updates = boom
    tracker.record = boom
    monkeypatch.setattr(CompileMonitor, "track", boom)
    monkeypatch.setattr(CompileMonitor, "track_aot", boom)
    monkeypatch.setattr(CompileMonitor, "note_aot_compile", boom)
    monkeypatch.setattr(CompileMonitor, "observe_flush", boom)
    monkeypatch.setattr(AnomalyDetector, "observe", boom)
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1
    )
    assert summary["fused_window"] == 4
    assert summary["updates"] == 4
    assert "goodput" not in summary


# ---------------------------------------------------------------------------
# Loader surface: device_epoch contract + env hardening.
# ---------------------------------------------------------------------------


def test_device_epoch_rejects_host_path_loader(world):
    _, _, _, ds = _pieces()
    loader = _loader(ds, world, device_gather=False)
    assert not loader.fusible()
    with pytest.raises(ValueError, match="device-gather"):
        loader.device_epoch()


def test_device_epoch_matches_iteration_order(world):
    # The fused pass must consume exactly the batches iterating would:
    # same permutation, same epoch bookkeeping.
    _, _, _, ds = _pieces()
    a = _loader(ds, world)
    b = _loader(ds, world)
    it_batches = [
        np.asarray(jax.device_get(batch[0])) for batch in a
    ]
    staged, perm, start = b.device_epoch()
    assert start == 0
    perm_h = np.asarray(jax.device_get(perm))
    data_x = np.asarray(jax.device_get(staged[0]))
    for i, ref in enumerate(it_batches):
        got = data_x[perm_h[i * 64:(i + 1) * 64]]
        np.testing.assert_array_equal(got, ref)
    b.note_consumed(len(it_batches))
    assert a.state_dict() == b.state_dict()


def test_device_gather_budget_env_hardening(world, monkeypatch):
    _, _, _, ds = _pieces()
    loader = _loader(ds, world)
    backing = loader._array_backing()
    monkeypatch.setenv("FLUXMPI_TPU_DEVICE_GATHER_MAX_BYTES", "256MiB")
    with pytest.warns(UserWarning, match="not an integer"):
        assert loader._use_device_gather(backing) is True  # default budget
    # A parseable tiny budget still disables the path (no warning).
    monkeypatch.setenv("FLUXMPI_TPU_DEVICE_GATHER_MAX_BYTES", "16")
    assert loader._use_device_gather(backing) is False


def test_compile_cache_wiring(world, monkeypatch):
    # On the CPU test backend the persistent cache must refuse (stale
    # XLA:CPU entries can SIGILL) — silently for the implicit default,
    # loudly when explicitly requested; the init() spec plumbing mirrors
    # the other planes.
    from fluxmpi_tpu import runtime

    monkeypatch.delenv("FLUXMPI_TPU_COMPILE_CACHE", raising=False)
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")  # implicit call: no warning
        assert runtime.enable_compile_cache() is False
    with pytest.warns(UserWarning, match="TPU-only"):
        assert runtime.enable_compile_cache("/tmp/cache") is False
    monkeypatch.setenv("FLUXMPI_TPU_COMPILE_CACHE", "/tmp/cache")
    with pytest.warns(UserWarning, match="TPU-only"):
        runtime._configure_compile_cache(None)
    monkeypatch.delenv("FLUXMPI_TPU_COMPILE_CACHE", raising=False)
    runtime._configure_compile_cache(None)  # unset env: no-op
    runtime._configure_compile_cache(False)  # explicit off: no-op
    with pytest.raises(ValueError, match="compile_cache"):
        runtime._configure_compile_cache(0.5)
    # init() replay applies the spec (idempotent path).
    with pytest.warns(UserWarning, match="TPU-only"):
        fm.init(compile_cache="/tmp/cache")


@pytest.mark.parametrize(
    "jax_env, flux_env, arg, want",
    [
        # JAX_COMPILATION_CACHE_DIR set: the cache lives there and the
        # code sets no directory at all, whatever else asks for one.
        ("/some/dir", None, None, None),
        ("/some/dir", "/flux/dir", "/arg/dir", None),
        # Unset: <checkout>/.jax_cache, resolved from the package's own
        # location — never /tmp, a pid or a time.
        (None, None, None, "<checkout>/.jax_cache"),
        (None, "/flux/dir", None, "/flux/dir"),
        (None, "/flux/dir", "/arg/dir", "/arg/dir"),
    ],
)
def test_compile_cache_placement(monkeypatch, jax_env, flux_env, arg, want):
    """Where the persistent cache goes, on the one backend that gets one
    (the TPU branch, steered here; config updates are recorded, not
    applied — XLA:CPU must never get a persistent cache)."""
    import os

    import fluxmpi_tpu
    from fluxmpi_tpu import runtime

    for var, val in (("JAX_COMPILATION_CACHE_DIR", jax_env),
                     ("FLUXMPI_TPU_COMPILE_CACHE", flux_env)):
        if val is None:
            monkeypatch.delenv(var, raising=False)
        else:
            monkeypatch.setenv(var, val)
    updates = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    assert runtime.enable_compile_cache(arg) is True
    if want is None:
        assert "jax_compilation_cache_dir" not in updates
        return
    checkout = os.path.dirname(os.path.dirname(fluxmpi_tpu.__file__))
    assert updates["jax_compilation_cache_dir"] == want.replace(
        "<checkout>", checkout
    )
    assert os.path.exists(os.path.join(checkout, "chip_smoke.py"))


def test_fused_respects_tiny_budget_fallback(world, monkeypatch):
    # Auto mode: dataset over the staging budget -> host path -> the
    # fused window quietly disengages.
    loss_fn, opt, params, ds = _pieces()
    step = make_train_step(loss_fn, opt, mesh=world)
    monkeypatch.setenv("FLUXMPI_TPU_DEVICE_GATHER_MAX_BYTES", "16")
    _, summary = train_loop(
        step, _fresh(params, opt, world), _loader(ds, world), epochs=1
    )
    assert summary["fused_window"] is None
    assert summary["updates"] == 4
