"""Run-health plane tests (PR 7): goodput/badput accounting, live MFU
from the shared FLOPs helpers, anomaly detection with warn/halt
policies + diagnostics bundles, the zero-cost-when-off contract in
train_loop, the monitor's heartbeat staleness + goodput fold, the
goodput.*/anomaly.* schema namespaces, and the goodput_report CLI."""

import json
import math
import os
import subprocess
import sys
import threading
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import fluxmpi_tpu as fm
from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
from fluxmpi_tpu.parallel.train import replicate
from fluxmpi_tpu.telemetry import (
    AnomalyDetector,
    GoodputTracker,
    JSONLSink,
    MetricsRegistry,
    anomaly,
    goodput,
)
from fluxmpi_tpu.telemetry import schema as tschema
from fluxmpi_tpu.utils import flops as flops_util

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPORT = os.path.join(_REPO, "scripts", "goodput_report.py")
_CHECKER = os.path.join(_REPO, "scripts", "check_metrics_schema.py")


def _fake_clock(*ticks):
    """Deterministic clock: yields the given stamps in order (the
    watchdog's injectable-clock test discipline — no real sleeps)."""
    it = iter(ticks)
    return lambda: next(it)


@pytest.fixture()
def plane_off():
    """Guarantee the run-health plane is fully off around a test and
    restore whatever tracker/detector was installed before."""
    prev_tracker = goodput.set_goodput_tracker(GoodputTracker(enabled=False))
    prev_detector = anomaly.set_anomaly_detector(None)
    try:
        yield
    finally:
        goodput.set_goodput_tracker(prev_tracker)
        anomaly.set_anomaly_detector(prev_detector)


# ---------------------------------------------------------------------------
# FLOPs/MFU helpers of the live-MFU plane (utils.flops)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "device_kind, peak",
    [
        ("TPU v5 lite", 197e12),
        ("TPU v5e", 197e12),
        ("TPU v4", 275e12),
        ("TPU v6 lite", 918e12),
        # Substring lookup: "TPU v5p" must hit the v5p row, not the
        # "v5 lite"/v5e one.
        ("TPU v5p", 459e12),
        ("cpu", None),
    ],
)
def test_chip_peak_flops_lookup(device_kind, peak):
    assert flops_util.chip_peak_flops(device_kind) == peak


@pytest.mark.parametrize(
    "flops, rate, n_dev, device_kind, expected",
    [
        # 1e12 FLOPs/step at 98.5 steps/s on one v5e (197e12 peak) = 50%.
        (1e12, 98.5, 1, "TPU v5 lite", 0.5),
        # Per-chip normalization.
        (2e12, 98.5, 2, "TPU v5 lite", 0.5),
        # Unknown chip or missing FLOPs -> None.
        (1e12, 10.0, 1, "cpu", None),
        (None, 10.0, 1, "TPU v5 lite", None),
        (0.0, 10.0, 1, "TPU v5 lite", None),
    ],
)
def test_mfu_math(flops, rate, n_dev, device_kind, expected):
    assert flops_util.mfu(flops, rate, n_dev, device_kind) == expected


def test_mfu_raw_returns_impossible_values_for_caller_decision():
    # The helper reports the raw number; discarding is the caller's
    # policy.
    raw = flops_util.mfu(1e12, 1000.0, 1, "TPU v5 lite")
    assert raw is not None and raw > 1.0
    assert flops_util.mfu(None, 10.0, 1, "TPU v5 lite") is None
    assert flops_util.mfu(1e12, 10.0, 1, "cpu") is None
    # peak= override bypasses the device-kind table (live-tracker hook).
    assert flops_util.mfu(1e12, 98.5, 1, peak=197e12) == 0.5
    assert flops_util.mfu(1e12, 98.5, 1, None) is None


# ---------------------------------------------------------------------------
# GoodputTracker
# ---------------------------------------------------------------------------


def test_tracker_buckets_sum_to_wall_with_idle_remainder():
    clock = _fake_clock(0.0, 0.0, 1.0, 2.0, 3.0, 10.0)
    t = GoodputTracker(clock=clock)
    t.start_run()  # 0.0
    with t.segment("step"):  # 0.0 -> 1.0
        pass
    with t.segment("checkpoint_save"):  # 2.0 -> 3.0
        pass
    rep = t.report()  # wall = 10.0
    assert rep["wall_seconds"] == 10.0
    assert rep["buckets"]["step"] == 1.0
    assert rep["buckets"]["checkpoint_save"] == 1.0
    assert rep["buckets"]["host_idle"] == pytest.approx(8.0)
    assert sum(rep["buckets"].values()) == pytest.approx(rep["wall_seconds"])
    assert rep["goodput_fraction"] == pytest.approx(0.1)


def test_tracker_nested_segments_count_once():
    # resume wrapping checkpoint_restore must not double-book the wall:
    # only the outermost segment records.
    clock = _fake_clock(0.0, 0.0, 1.0, 2.0, 5.0, 5.0)
    t = GoodputTracker(clock=clock)
    t.start_run()
    with t.segment("resume"):  # 0.0 -> 5.0
        with t.segment("checkpoint_restore"):  # 1.0 -> 2.0, swallowed
            pass
    rep = t.report()
    assert rep["buckets"]["resume"] == 5.0
    assert "checkpoint_restore" not in rep["buckets"]


def test_tracker_ignores_other_threads():
    # A background async-checkpoint thread overlaps the driver's wall
    # clock — booking it would sum buckets past the wall.
    t = GoodputTracker()
    t.start_run()
    t.add("step", 1.0)

    def background():
        with t.segment("checkpoint_save"):
            pass
        t.add("checkpoint_save", 99.0)

    th = threading.Thread(target=background)
    th.start()
    th.join()
    assert t.bucket_seconds("checkpoint_save") == 0.0
    assert t.bucket_seconds("step") == 1.0


def test_tracker_disabled_reads_no_clock():
    def boom():
        raise AssertionError("clock read on the disabled path")

    t = GoodputTracker(clock=boom, enabled=False)
    assert t.segment("step") is t.segment("other")  # shared no-op
    with t.segment("step"):
        pass
    t.add("step", 1.0)
    assert t.bucket_seconds("step") == 0.0


def test_tracker_mfu_uses_shared_helper():
    # Live MFU is utils.flops.mfu of the tracker's FLOPs and rate.
    clock = _fake_clock(0.0, 0.0, 2.0, 10.0)
    t = GoodputTracker(clock=clock, peak_flops_per_chip=197e12, n_chips=8)
    t.start_run()
    with t.segment("step"):  # 2.0s productive
        pass
    t.note_updates(50)
    t.set_flops_per_update(1e12)
    rep = t.report()  # wall = 10.0
    assert rep["mfu_productive"] == flops_util.mfu(
        1e12, 50 / 2.0, 8, "TPU v5 lite"
    )
    assert rep["mfu"] == flops_util.mfu(1e12, 50 / 10.0, 8, "TPU v5 lite")
    assert rep["mfu"] < rep["mfu_productive"]  # badput drags wall MFU


def test_tracker_record_flushes_schema_valid_goodput_metrics():
    reg = MetricsRegistry()
    clock = _fake_clock(0.0, 0.0, 1.0, 4.0, 4.0)
    t = GoodputTracker(registry=reg, clock=clock)
    t.start_run()
    with t.segment("step"):
        pass
    t.note_updates(10)
    t.record()
    assert reg.gauge("goodput.bucket_seconds", bucket="step").value == 1.0
    assert reg.gauge("goodput.fraction").value == pytest.approx(0.25)
    assert reg.gauge("goodput.updates").value == 10.0
    record = reg.flush()
    assert tschema.validate_record(record) == []
    # Disabled registry: record() is a no-op (zero-cost contract).
    reg.enabled = False
    try:
        before = reg.gauge("goodput.updates").value
        t.note_updates(5)
        t.record()
        assert reg.gauge("goodput.updates").value == before
    finally:
        reg.enabled = True


def test_goodput_configure_env_and_shutdown(monkeypatch, plane_off):
    tr = goodput.get_goodput_tracker()
    monkeypatch.delenv("FLUXMPI_TPU_GOODPUT", raising=False)
    assert goodput.configure() is tr and not tr.enabled
    monkeypatch.setenv("FLUXMPI_TPU_GOODPUT", "1")
    assert goodput.configure().enabled
    monkeypatch.setenv("FLUXMPI_TPU_GOODPUT", "0")
    assert not goodput.configure().enabled
    custom = GoodputTracker(enabled=False)
    assert goodput.configure(custom) is custom and custom.enabled
    assert goodput.get_goodput_tracker() is custom
    with pytest.raises(ValueError, match="goodput spec"):
        goodput.configure("bogus")
    custom.add("step", 1.0)
    goodput.shutdown()
    assert not custom.enabled
    assert custom.bucket_seconds("step") == 0.0  # run state dropped


# ---------------------------------------------------------------------------
# AnomalyDetector
# ---------------------------------------------------------------------------


def test_anomaly_nan_halts_and_writes_bundle(tmp_path):
    reg = MetricsRegistry()
    det = AnomalyDetector(registry=reg, dump_dir=str(tmp_path))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        events = det.observe(loss=float("nan"), step=7)
    assert [e["rule"] for e in events] == ["nan_loss"]
    assert events[0]["action"] == "halt"
    assert events[0]["step"] == 7
    assert reg.counter("anomaly.triggered", rule="nan_loss").value == 1.0
    bundle_path = det.last_dump_path
    assert bundle_path is not None and os.path.exists(bundle_path)
    with open(bundle_path) as f:
        text = f.read()
    # STRICT JSON: the NaN trigger value must serialize as null +
    # value_repr, never as the bare `NaN` token Perfetto/jq reject.
    def _no_constants(name):
        raise AssertionError(f"non-strict JSON constant {name!r} in bundle")

    bundle = json.loads(text, parse_constant=_no_constants)
    assert bundle["anomaly"]["value"] is None
    assert bundle["anomaly"]["value_repr"] == "nan"
    # The bundle IS a watchdog_dump record (thread stacks, flight tail,
    # registry flush) + the anomaly section — one validator covers both.
    assert tschema.validate_watchdog_dump(bundle) == []
    assert bundle["anomaly"]["rule"] == "nan_loss"
    assert bundle["reason"] == "anomaly:nan_loss"


def test_anomaly_nan_grad_and_policy_override(tmp_path):
    det = AnomalyDetector(
        policies={"nan_grad": "warn", "nan_loss": "off"},
        dump_dir=str(tmp_path),
        dump=False,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        events = det.observe(loss=float("inf"), grad_norm=float("nan"))
    # nan_loss is off; nan_grad downgraded to warn.
    assert [(e["rule"], e["action"]) for e in events] == [("nan_grad", "warn")]
    with pytest.raises(ValueError, match="unknown anomaly rule"):
        AnomalyDetector(policies={"bogus": "warn"})
    with pytest.raises(ValueError, match="policy"):
        AnomalyDetector(policies={"nan_loss": "explode"})


def test_anomaly_loss_spike_zscore_after_warmup():
    det = AnomalyDetector(
        warmup=5, spike_zscore=4.0, ewma_alpha=0.5, dump=False
    )
    rng = np.random.default_rng(0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(20):  # stable baseline, no triggers
            assert det.observe(loss=1.0 + 0.01 * rng.standard_normal()) == []
        events = det.observe(loss=50.0, step=21)
    assert [e["rule"] for e in events] == ["loss_spike"]
    assert events[0]["value"] > 4.0  # the z-score rides the event


def test_anomaly_spike_quiet_during_warmup():
    det = AnomalyDetector(warmup=5, dump=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert det.observe(loss=1.0) == []
        assert det.observe(loss=1000.0) == []  # within warmup: armed later


def test_anomaly_step_time_regression_and_data_stall():
    det = AnomalyDetector(
        warmup=3, step_time_factor=2.0, data_stall_factor=1.0, dump=False
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(5):
            assert det.observe(step_seconds=0.1) == []
        events = det.observe(step_seconds=0.5, step=6)
        assert [e["rule"] for e in events] == ["step_time_regression"]
        # The loader wait is PART of the wall step time, so the rule
        # judges it against the compute remainder: 0.06s wait vs 0.04s
        # compute = input-bound, 0.02s wait vs 0.08s compute = healthy.
        events = det.observe(
            step_seconds=0.1, fetch_seconds=0.06, step=7
        )
        assert "data_stall" in [e["rule"] for e in events]
        events = det.observe(
            step_seconds=0.1, fetch_seconds=0.02, step=8
        )
        assert "data_stall" not in [e["rule"] for e in events]
        # All-wait interval (compute remainder 0) triggers too.
        events = det.observe(
            step_seconds=0.1, fetch_seconds=0.1, step=9
        )
    assert "data_stall" in [e["rule"] for e in events]
    assert all(math.isfinite(e["value"]) for e in events)


def test_anomaly_instant_rides_trace_and_validates(tmp_path):
    from fluxmpi_tpu.telemetry import tracing

    tracer = tracing.Tracer(enabled=True)
    prev = tracing.set_tracer(tracer)
    try:
        det = AnomalyDetector(dump_dir=str(tmp_path), dump=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            det.observe(loss=float("nan"), step=3)
        export = tracer.export()
        assert tschema.validate_trace_export(export) == []
        instants = [
            ev
            for ev in export["traceEvents"]
            if ev.get("name") == "anomaly.nan_loss"
        ]
        assert len(instants) == 1
        assert instants[0]["ph"] in ("i", "I")
        assert instants[0]["args"]["step"] == 3
        assert instants[0]["args"]["rule"] == "nan_loss"
    finally:
        tracing.set_tracer(prev)


def test_anomaly_event_schema_rejects_wrong_phase():
    ev = {"name": "anomaly.nan_loss", "ph": "X", "ts": 1.0, "dur": 2.0,
          "pid": 1, "tid": 1, "args": {"step": 3, "rule": "nan_loss"}}
    assert any("instant" in e for e in tschema.validate_trace_event(ev))
    ev = {"name": "anomaly.nan_loss", "ph": "i", "ts": 1.0, "pid": 1,
          "tid": 1, "args": {"rule": "nan_loss"}}
    assert any("args.step" in e for e in tschema.validate_trace_event(ev))


def test_goodput_namespace_is_closed():
    m = {"name": "goodput.bogus", "type": "gauge", "labels": {}, "value": 1.0}
    assert any(
        "framework-owned" in e for e in tschema.validate_metric(m)
    )
    m = {"name": "anomaly.triggered", "type": "counter",
         "labels": {"rule": "nan_loss"}, "value": 1.0}
    assert tschema.validate_metric(m) == []


def test_anomaly_configure_forms(plane_off):
    assert anomaly.configure() is None  # env unset: plane stays off
    det = anomaly.configure(True)
    assert det is not None and anomaly.get_anomaly_detector() is det
    assert anomaly.configure(True) is det  # idempotent replay keeps state
    warn_det = anomaly.configure("warn")
    assert all(p in ("warn", "off") for p in warn_det.policies.values())
    # configure(True) after "warn" must deliver True's documented
    # defaults (NaN halts) — not silently keep the observe-only one.
    halting = anomaly.configure(True)
    assert halting is not warn_det
    assert halting.policies["nan_loss"] == "halt"
    assert anomaly.configure(False) is None
    assert anomaly.get_anomaly_detector() is None
    with pytest.raises(ValueError, match="anomaly spec"):
        anomaly.configure("bogus")
    anomaly.configure(True)
    anomaly.shutdown()
    assert anomaly.get_anomaly_detector() is None


# ---------------------------------------------------------------------------
# TrainingMonitor: heartbeat staleness + goodput fold
# ---------------------------------------------------------------------------


def test_monitor_heartbeat_age_with_injected_clock(world):
    from fluxmpi_tpu.telemetry import TrainingMonitor

    reg = MetricsRegistry()
    mon = TrainingMonitor(reg, interval=1, cross_host=False,
                          clock=_fake_clock(100.0, 107.5, 109.0))
    mon.collect()
    assert reg.gauge("monitor.heartbeat_age_seconds").value == 0.0
    assert reg.gauge("monitor.heartbeat_unix").value == 100.0
    mon.collect()
    assert reg.gauge("monitor.heartbeat_age_seconds").value == 7.5
    mon.collect()
    assert reg.gauge("monitor.heartbeat_age_seconds").value == 1.5


def test_monitor_folds_goodput_fraction(world, plane_off):
    from fluxmpi_tpu.telemetry import TrainingMonitor

    tracker = GoodputTracker(clock=_fake_clock(0.0, 0.0, 3.0, 4.0))
    tracker.start_run()
    with tracker.segment("step"):  # 3s productive of 4s wall
        pass
    goodput.set_goodput_tracker(tracker)
    reg = MetricsRegistry()
    mon = TrainingMonitor(reg, interval=1, cross_host=False)
    summary = mon.observe_step(0.01)  # interval=1: collects immediately
    assert reg.gauge("monitor.goodput_fraction_mean").value == pytest.approx(
        0.75
    )
    assert summary["goodput_fraction_min"] == pytest.approx(0.75)
    # Plane off: no goodput gauges ride the collect.
    goodput.set_goodput_tracker(GoodputTracker(enabled=False))
    reg2 = MetricsRegistry()
    mon2 = TrainingMonitor(reg2, interval=1, cross_host=False)
    summary2 = mon2.observe_step(0.01)
    assert "goodput_fraction_min" not in summary2
    assert all(
        m["name"] != "monitor.goodput_fraction_mean"
        for m in reg2.snapshot()
    )


# ---------------------------------------------------------------------------
# train_loop wiring
# ---------------------------------------------------------------------------


def _mlp_pieces(n=256, nan_from=None):
    from fluxmpi_tpu.models import MLP

    model = MLP(features=(16, 16, 1))

    def loss_fn(p, ms, b):
        bx, by = b
        return jnp.mean((model.apply(p, bx) - by) ** 2), ms

    opt = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    x = rng.uniform(-2, 2, size=(n, 1)).astype(np.float32)
    y = (x**2).astype(np.float32)
    if nan_from is not None:
        y[nan_from:] = np.nan
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1)))
    )
    return loss_fn, opt, params, ArrayDataset((x, y))


def test_train_loop_fully_off_plane_costs_nothing(world, plane_off):
    """The PR 4 monkeypatch-explode contract extended to the run-health
    plane: with goodput disabled and no detector installed, the hot loop
    performs no tracker clock reads, no segment/bucket work, and no
    anomaly observes."""
    tracker = goodput.get_goodput_tracker()
    assert not tracker.enabled
    assert anomaly.get_anomaly_detector() is None

    def boom(*a, **k):
        raise AssertionError("run-health plane touched on the off path")

    tracker._clock = boom
    tracker.segment = boom
    tracker.add = boom
    tracker.note_updates = boom
    tracker.record = boom
    orig_observe = AnomalyDetector.observe
    AnomalyDetector.observe = boom
    try:
        loss_fn, opt, params, ds = _mlp_pieces()
        loader = DistributedDataLoader(ds, 64, mesh=world)
        step = make_train_step(loss_fn, opt, mesh=world)
        state, summary = train_loop(
            step, replicate(TrainState.create(params, opt, None), world),
            loader, epochs=1,
        )
    finally:
        AnomalyDetector.observe = orig_observe
    assert summary["updates"] == 4
    assert summary["anomaly"] is None
    assert "goodput" not in summary


def test_train_loop_goodput_accounting(world, plane_off):
    tracker = GoodputTracker()
    goodput.set_goodput_tracker(tracker)
    loss_fn, opt, params, ds = _mlp_pieces()
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    reg = MetricsRegistry()
    state, summary = train_loop(
        step, replicate(TrainState.create(params, opt, None), world),
        loader, epochs=2, flush_every=3, metrics=reg,
    )
    rep = summary["goodput"]
    assert rep["updates"] == summary["updates"] == 8
    # Compile, productive dispatch, and loader waits were all observed.
    assert rep["buckets"]["compile"] > 0
    assert rep["buckets"]["step"] > 0
    assert rep["buckets"]["data_stall"] > 0
    # Measured buckets can never exceed the wall; with the computed
    # host_idle remainder they sum to it exactly.
    measured = sum(
        v for k, v in rep["buckets"].items() if k != "host_idle"
    )
    assert measured <= rep["wall_seconds"] + 1e-6
    assert sum(rep["buckets"].values()) == pytest.approx(
        rep["wall_seconds"], rel=1e-6
    )
    assert 0.0 <= rep["goodput_fraction"] <= 1.0
    # goodput.* gauges landed in the loop's registry at flush time.
    assert reg.gauge("goodput.updates").value == 8.0
    assert (
        reg.gauge("goodput.bucket_seconds", bucket="step").value
        == pytest.approx(rep["buckets"]["step"], rel=1e-3)
    )
    # FLOPs came from the cost-model helper (utils.flops).
    assert rep["flops_per_update"] is None or rep["flops_per_update"] > 0


def test_train_loop_resets_tracker_window_per_run(world, plane_off):
    # A second train_loop in the same process gets a FRESH goodput
    # window: no inherited buckets, no inter-run gap booked as
    # host_idle, no MFU computed from the first run's FLOPs.
    tracker = GoodputTracker()
    goodput.set_goodput_tracker(tracker)
    loss_fn, opt, params, ds = _mlp_pieces()
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    _, s1 = train_loop(
        step, replicate(TrainState.create(params, opt, None), world),
        loader, epochs=1,
    )
    tracker.add("checkpoint_save", 1e6)  # inter-run noise to shed
    _, s2 = train_loop(
        step, replicate(TrainState.create(params, opt, None), world),
        loader, epochs=1,
    )
    assert s2["goodput"]["updates"] == 4  # not cumulative 8
    assert s2["goodput"]["buckets"].get("checkpoint_save", 0.0) == 0.0
    assert s2["goodput"]["wall_seconds"] < s1["goodput"]["wall_seconds"] + 60


def test_train_loop_live_mfu_matches_flops_formula(world, plane_off):
    # Acceptance: the loop's live MFU is utils.flops.mfu of the FLOPs
    # utils.flops.cost_analysis_flops read and the rate the tracker
    # measured. (CPU has no peak-FLOPs entry, so the tracker gets the
    # v5e peak injected — the formula, not the table, is under test.)
    tracker = GoodputTracker(peak_flops_per_chip=197e12, n_chips=8)
    goodput.set_goodput_tracker(tracker)
    loss_fn, opt, params, ds = _mlp_pieces()
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    state, summary = train_loop(
        step, replicate(TrainState.create(params, opt, None), world),
        loader, epochs=1,
    )
    rep = summary["goodput"]
    if rep["flops_per_update"] is None:
        pytest.skip("XLA cost analysis unavailable on this backend")
    step_s = rep["buckets"]["step"]
    by_formula = flops_util.mfu(
        rep["flops_per_update"],
        rep["updates"] / step_s,
        8,
        "TPU v5 lite",  # same 197e12 peak the tracker was given
    )
    assert rep["mfu_productive"] == by_formula


def test_train_loop_nan_halts_cleanly_with_bundle(world, tmp_path, plane_off):
    """End-to-end acceptance: goodput+anomaly on, a checkpoint save, a
    synthetic NaN — the loop halts deterministically at the flush that
    sees it, the JSONL passes the schema checker, the bundle lands on
    disk, and the buckets account for the wall."""
    from fluxmpi_tpu.telemetry import tracing

    jsonl = str(tmp_path / "run.jsonl")
    reg = MetricsRegistry(sinks=[JSONLSink(jsonl)])
    goodput.set_goodput_tracker(GoodputTracker())
    anomaly.set_anomaly_detector(
        AnomalyDetector(dump_dir=str(tmp_path), registry=reg)
    )
    tracer = tracing.Tracer(enabled=True)
    prev_tracer = tracing.set_tracer(tracer)
    from fluxmpi_tpu.utils import CheckpointManager

    # Batches 1-3 finite, batch 4 NaN (shuffle off): flush_every=2 sees
    # a finite interval at update 2 (where save_every=2 banks a good
    # checkpoint) and the NaN at update 4 -> halt, no further saves.
    loss_fn, opt, params, ds = _mlp_pieces(nan_from=192)
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            state, summary = train_loop(
                step, replicate(TrainState.create(params, opt, None), world),
                loader, epochs=4, flush_every=2, metrics=reg,
                checkpoint=mgr, save_every=2,
            )
    finally:
        tracing.set_tracer(prev_tracer)
    # Deterministic halt at the first NaN flush — not after 4 epochs.
    assert summary["anomaly"] == "nan_loss"
    assert summary["updates"] == 4
    assert math.isnan(summary["loss"])
    # The save at the halting boundary was skipped: only the known-good
    # step-2 checkpoint exists.
    assert mgr.all_steps() == [2]
    # Diagnostics bundle on disk, schema-valid, naming the rule.
    bundle_file = tmp_path / "fluxmpi_anomaly.0.json"
    assert bundle_file.exists()
    bundle = json.loads(bundle_file.read_text())
    assert tschema.validate_watchdog_dump(bundle) == []
    assert bundle["anomaly"]["rule"] == "nan_loss"
    # anomaly.triggered rode the metrics plane.
    assert reg.counter("anomaly.triggered", rule="nan_loss").value >= 1.0
    # ...and the anomaly.nan_loss instant rode the trace timeline, at
    # the halting update count, in a schema-valid export.
    export = tracer.export()
    assert tschema.validate_trace_export(export) == []
    instants = [
        ev for ev in export["traceEvents"]
        if ev.get("name") == "anomaly.nan_loss"
    ]
    assert len(instants) == 1
    assert instants[0]["args"]["step"] == 4
    # Goodput accounting: checkpoint save time attributed, buckets sum
    # to wall within tolerance.
    rep = summary["goodput"]
    assert rep["buckets"]["checkpoint_save"] > 0
    assert sum(rep["buckets"].values()) == pytest.approx(
        rep["wall_seconds"], rel=1e-6
    )
    # The emitted JSONL (goodput.* + anomaly.* + train.*) validates.
    reg.close()
    proc = subprocess.run(
        [sys.executable, _CHECKER, jsonl], capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stderr
    # And the report CLI reads it back with matching totals.
    proc = subprocess.run(
        [sys.executable, _REPORT, jsonl, "--json"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    agg = json.loads(proc.stdout)
    assert agg["host_count"] == 1
    assert agg["updates"] == 4
    assert agg["buckets"]["checkpoint_save"] > 0


def test_train_loop_warn_policy_does_not_halt(world, plane_off):
    anomaly.set_anomaly_detector(
        AnomalyDetector(
            policies={"nan_loss": "warn", "nan_grad": "warn"}, dump=False
        )
    )
    loss_fn, opt, params, ds = _mlp_pieces(nan_from=0)  # NaN from step 1
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        state, summary = train_loop(
            step, replicate(TrainState.create(params, opt, None), world),
            loader, epochs=2, flush_every=2,
        )
    assert summary["anomaly"] is None  # warned, never halted
    assert summary["updates"] == 8  # full budget ran
    det = anomaly.get_anomaly_detector()
    assert any(e["rule"] == "nan_loss" for e in det.triggered)


def test_train_loop_preemption_with_halt_skips_emergency_save(
    world, tmp_path, plane_off
):
    # A preemption coinciding with a halt-policy anomaly must NOT bank
    # the diverged state as the newest restorable checkpoint — the
    # emergency save is gated like the periodic ones.
    from fluxmpi_tpu.utils import CheckpointManager

    anomaly.set_anomaly_detector(AnomalyDetector(dump=False))
    loss_fn, opt, params, ds = _mlp_pieces(nan_from=0)  # NaN from step 1
    loader = DistributedDataLoader(ds, 64, mesh=world)
    step = make_train_step(loss_fn, opt, mesh=world)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    fm.request_preemption()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            state, summary = train_loop(
                step, replicate(TrainState.create(params, opt, None), world),
                loader, epochs=2, flush_every=1, checkpoint=mgr,
            )
    finally:
        fm.clear_preemption()
    assert summary["preempted"] is True
    assert summary["anomaly"] == "nan_loss"
    assert mgr.all_steps() == []  # no NaN checkpoint banked


# ---------------------------------------------------------------------------
# goodput_report.py CLI
# ---------------------------------------------------------------------------


def test_goodput_report_smoke(tmp_path):
    jsonl = tmp_path / "hosts.jsonl"
    reg = MetricsRegistry(sinks=[JSONLSink(str(jsonl))])
    clock = _fake_clock(0.0, 0.0, 8.0, 9.0, 10.0, 10.0)
    t = GoodputTracker(registry=reg, clock=clock)
    t.start_run()
    with t.segment("step"):  # 8s
        pass
    with t.segment("checkpoint_save"):  # 1s
        pass
    t.note_updates(100)
    t.record()
    reg.flush()
    reg.close(flush=False)
    proc = subprocess.run(
        [sys.executable, _REPORT, str(jsonl)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "goodput 80.0%" in proc.stdout
    assert "checkpoint_save" in proc.stdout
    assert "updates 100" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _REPORT, str(jsonl), "--json"],
        capture_output=True, text=True,
    )
    agg = json.loads(proc.stdout)
    assert agg["wall_seconds"] == pytest.approx(10.0)
    assert agg["goodput_fraction"] == pytest.approx(0.8)
    assert agg["buckets"]["step"] == pytest.approx(8.0)


def test_goodput_report_tolerates_torn_line(tmp_path):
    # A host killed mid-write leaves a truncated final line — the very
    # post-mortem this report serves must not refuse the fleet's data.
    jsonl = tmp_path / "torn.jsonl"
    reg = MetricsRegistry(sinks=[JSONLSink(str(jsonl))])
    t = GoodputTracker(registry=reg, clock=_fake_clock(0.0, 0.0, 4.0, 5.0))
    t.start_run()
    with t.segment("step"):
        pass
    t.record()
    reg.flush()
    reg.close(flush=False)
    with open(jsonl, "a", encoding="utf-8") as f:
        f.write('{"schema": "fluxmpi_tpu.telemetry/v1", "time_un')  # torn
    proc = subprocess.run(
        [sys.executable, _REPORT, str(jsonl)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "skipping" in proc.stderr
    assert "goodput 80.0%" in proc.stdout


def test_goodput_report_exit_codes(tmp_path):
    # No goodput metrics anywhere -> exit 1 with a pointed message.
    plain = tmp_path / "plain.jsonl"
    reg = MetricsRegistry(sinks=[JSONLSink(str(plain))])
    reg.counter("train.steps").inc()
    reg.flush()
    reg.close(flush=False)
    proc = subprocess.run(
        [sys.executable, _REPORT, str(plain)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "FLUXMPI_TPU_GOODPUT" in proc.stderr
    # Unreadable input -> exit 2.
    proc = subprocess.run(
        [sys.executable, _REPORT, str(tmp_path / "missing.jsonl")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 2


def test_goodput_report_watch_rerenders_midrun(tmp_path):
    """--watch N: the report becomes a mid-run monitor — it re-renders
    from the growing bank on an interval (same parse path), and a bank
    that has no data YET is a waiting state, not an error."""
    jsonl = tmp_path / "live.jsonl"
    reg = MetricsRegistry(sinks=[JSONLSink(str(jsonl))])
    t = GoodputTracker(registry=reg, clock=_fake_clock(0.0, 0.0, 8.0, 10.0))
    t.start_run()
    with t.segment("step"):  # 8s of a 10s wall
        pass
    t.record()
    reg.flush()
    reg.close(flush=False)
    proc = subprocess.run(
        [sys.executable, _REPORT, str(jsonl),
         "--watch", "0.05", "--watch-count", "2"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.count("run: 1 host stream(s)") == 2  # re-rendered
    assert "goodput 80.0%" in proc.stdout
    # Missing file: the run may simply not have flushed yet — waiting,
    # exit 0 (one-shot mode keeps its hard exit 2 for the post-mortem).
    proc = subprocess.run(
        [sys.executable, _REPORT, str(tmp_path / "nonexistent.jsonl"),
         "--watch", "0.05", "--watch-count", "1"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "waiting" in proc.stderr
