"""Fault-injection harness tests: spec grammar, deterministic firing,
the zero-cost-when-off fast-guard, the woven comm/data/checkpoint sites,
crash-consistent checkpoint commit + quarantine, retry backoff, and the
schema extensions. All tier-1 fast: no sleeps (the retry sleep is
injected), no subprocesses."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import fluxmpi_tpu as fm
from fluxmpi_tpu import faults
from fluxmpi_tpu.errors import (
    CheckpointDesyncError,
    CheckpointTimeoutError,
    FaultInjectedError,
)
from fluxmpi_tpu.telemetry import MetricsRegistry, set_registry, get_registry


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# The grammar/semantics tests below arm synthetic sites that are not
# woven into the framework; install()/scope() now validate against
# faults.KNOWN_SITES, so register them the way user-woven sites would be.
for _site in (
    "site.a",
    "site.b",
    "site.p",
    "site.c",
    "site.r",
    "site.x",
    "site.m",
    "outer.site",
    "inner.site",
):
    faults.register_site(_site)


# ---------------------------------------------------------------------------
# Grammar / schedule semantics
# ---------------------------------------------------------------------------


def test_parse_spec_grammar():
    s = faults.parse_spec("comm.allreduce@step=7")
    assert (s.site, s.step, s.times, s.p) == ("comm.allreduce", 7, 1, None)
    s = faults.parse_spec("ckpt.write:p=0.1:seed=5")
    assert (s.site, s.p, s.seed, s.times) == ("ckpt.write", 0.1, 5, None)
    s = faults.parse_spec("data.fetch@step=3:times=2:proc=1")
    assert (s.step, s.times, s.proc) == (3, 2, 1)
    # @step sugar and :step spelling are equivalent.
    assert faults.parse_spec("x:step=3").step == faults.parse_spec("x@step=3").step


def test_parse_spec_rejects_bad_entries():
    with pytest.raises(ValueError, match="key=value"):
        faults.parse_spec("site:banana")
    with pytest.raises(ValueError, match="unknown fault modifier"):
        faults.parse_spec("site:frequency=2")
    with pytest.raises(ValueError, match="mutually exclusive"):
        faults.FaultSpec("s", step=2, p=0.5)
    with pytest.raises(ValueError, match="step must be >= 1"):
        faults.FaultSpec("s", step=0)
    with pytest.raises(ValueError, match=r"p must be in \[0, 1\]"):
        faults.FaultSpec("s", p=1.5)


def test_step_trigger_fires_once_at_exact_hit():
    faults.install("site.a@step=3")
    for _ in range(2):
        faults.check("site.a")  # hits 1, 2: no fire
    with pytest.raises(FaultInjectedError) as exc:
        faults.check("site.a")
    assert exc.value.site == "site.a" and exc.value.hit == 3
    faults.check("site.a")  # times=1 default: spent
    assert faults.injected_count() == 1


def test_times_widens_the_firing_window():
    faults.install("site.a@step=2:times=2")
    faults.check("site.a")
    for expected_hit in (2, 3):
        with pytest.raises(FaultInjectedError):
            faults.check("site.a")
    faults.check("site.a")  # both injections spent
    assert faults.injected_count() == 2


def test_bare_entry_fires_immediately_once():
    faults.install("site.b")
    with pytest.raises(FaultInjectedError):
        faults.check("site.b")
    faults.check("site.b")


def test_probability_mode_is_seeded_and_deterministic():
    def run(seed):
        fired = []
        with faults.scope(f"site.p:p=0.5:seed={seed}:times=1000"):
            for i in range(50):
                try:
                    faults.check("site.p")
                except FaultInjectedError:
                    fired.append(i)
        return fired

    a, b = run(7), run(7)
    assert a == b and 5 < len(a) < 45  # same draws, plausibly ~half
    assert run(8) != a  # a different seed is a different schedule


def test_proc_targeting_skips_other_processes():
    # Single-process world is index 0: proc=1 entries never fire here.
    faults.install("site.c@step=1:proc=1")
    faults.check("site.c")
    assert faults.injected_count() == 0
    faults.install("site.c@step=1:proc=0")
    with pytest.raises(FaultInjectedError):
        faults.check("site.c")


def test_env_configure_and_clear(monkeypatch):
    monkeypatch.setenv("FLUXMPI_TPU_FAULTS", "comm.allreduce@step=2, data.fetch:p=0.5")
    specs = faults.configure()
    assert [s.site for s in specs] == ["comm.allreduce", "data.fetch"]
    assert faults.ARMED
    faults.configure(False)
    assert not faults.ARMED and faults.active() == []
    monkeypatch.delenv("FLUXMPI_TPU_FAULTS")
    faults.configure()  # unset env: no-op, stays clear
    assert not faults.ARMED


def test_env_configure_replay_keeps_hit_counters(monkeypatch):
    # init() is documented idempotent: a replay that finds the SAME env
    # schedule armed must not reset hit counters or re-arm fired
    # times=1 entries (determinism contract).
    monkeypatch.setenv("FLUXMPI_TPU_FAULTS", "site.r@step=2")
    faults.configure()
    faults.check("site.r")  # hit 1: no fire
    faults.configure()  # idempotent init() replay
    with pytest.raises(FaultInjectedError):
        faults.check("site.r")  # still hit 2, not reset to 1
    faults.configure()  # replay after the entry fired: stays spent
    faults.check("site.r")  # hit 3, times=1 exhausted — no re-fire
    monkeypatch.setenv("FLUXMPI_TPU_FAULTS", "site.r@step=5")
    faults.configure()  # a CHANGED env schedule does install fresh
    faults.check("site.r")  # hit 1 of the new schedule
    assert faults.injected_count() == 0


def test_explicit_configure_replay_keeps_hit_counters():
    # Same contract for init(faults=...) replays as for the env route,
    # in any spelling: grammar string or FaultSpec objects.
    faults.configure("site.x@step=2")
    faults.check("site.x")  # hit 1: no fire
    faults.configure("site.x@step=2")  # idempotent init() replay
    faults.configure([faults.FaultSpec("site.x", step=2)])  # same, object
    with pytest.raises(FaultInjectedError):
        faults.check("site.x")  # still hit 2, counters kept
    faults.configure("site.x@step=9")  # changed spec installs fresh
    faults.check("site.x")  # hit 1 of the new schedule
    assert faults.injected_count() == 0


def test_scope_invalid_spec_leaves_schedule_armed():
    faults.install("outer.site@step=1")
    with pytest.raises(ValueError):
        with faults.scope("outer.site@step"):  # bad modifier
            pass
    # The previous schedule survives a failed __enter__ untouched.
    assert faults.ARMED
    assert [s.site for s in faults.active()] == ["outer.site"]
    with pytest.raises(FaultInjectedError):
        faults.check("outer.site")


def test_scope_restores_previous_schedule():
    faults.install("outer.site@step=1")
    with faults.scope("inner.site@step=1"):
        assert [s.site for s in faults.active()] == ["inner.site"]
    assert [s.site for s in faults.active()] == ["outer.site"]
    faults.clear()
    with faults.scope("inner.site@step=1"):
        assert faults.ARMED
    assert not faults.ARMED


def test_injected_counter_reaches_registry():
    reg = MetricsRegistry()
    old = get_registry()
    set_registry(reg)
    try:
        faults.install("site.m@step=1")
        with pytest.raises(FaultInjectedError):
            faults.check("site.m")
        assert reg.counter("fault.injected", site="site.m").value == 1
    finally:
        set_registry(old)


# ---------------------------------------------------------------------------
# Zero-cost-when-off: the fast-guard contract
# ---------------------------------------------------------------------------


def test_disarmed_harness_never_enters_check(world, monkeypatch):
    """With no schedule armed, the woven sites must not even CALL
    faults.check — the one-attribute-read guard is the whole cost."""
    def boom(site):
        raise AssertionError(f"check({site!r}) entered while disarmed")

    monkeypatch.setattr(faults, "check", boom)
    assert not faults.ARMED
    x = np.arange(8, dtype=np.float32)
    fm.allreduce(x)  # comm site guarded
    fm.barrier()
    fm.host_allreduce(np.float32(1.0))
    loader = fm.DistributedDataLoader(
        fm.ArrayDataset((np.ones((16, 2), np.float32),)), 8, mesh=world
    )
    for _ in loader:  # data site guarded
        pass


def test_armed_comm_site_fires_deterministically(world):
    x = np.arange(8, dtype=np.float32)
    with faults.scope("comm.allreduce@step=2"):
        fm.allreduce(x)  # hit 1: clean
        with pytest.raises(FaultInjectedError, match="comm.allreduce"):
            fm.allreduce(x)
        fm.allreduce(x)  # spent
        # bcast is a different site: untouched.
        fm.bcast(x)


@pytest.mark.parametrize(
    "site,call",
    [
        ("comm.allreduce", lambda x: fm.allreduce(x)),
        ("comm.bcast", lambda x: fm.bcast(x)),
        ("comm.reduce", lambda x: fm.reduce(x)),
        ("comm.barrier", lambda x: fm.barrier()),
        ("comm.host_allreduce", lambda x: fm.host_allreduce(np.float32(1))),
        ("comm.host_allgather", lambda x: fm.host_allgather(np.float32(1))),
        ("comm.host_bcast", lambda x: fm.host_bcast(np.float32(1))),
    ],
)
def test_every_comm_site_is_injectable(world, site, call):
    # Every comm.* entry of faults.KNOWN_SITES has a live trigger — the
    # coverage contract the fluxlint unregistered-fault-site rule greps
    # this file for (each registered site must be exercised somewhere in
    # tests/).
    x = np.arange(8, dtype=np.float32)
    with faults.scope(site + "@step=1"):
        with pytest.raises(FaultInjectedError, match=site):
            call(x)
    call(x)  # disarmed: clean


# ---------------------------------------------------------------------------
# Site-registry validation (install raises, configure warns)
# ---------------------------------------------------------------------------


def test_install_rejects_unknown_site_naming_nearest():
    with pytest.raises(ValueError, match=r"ckpt\.write"):
        faults.install("ckpt.wrte@step=1")  # typo: nearest is named
    assert not faults.ARMED  # nothing armed by the failed install


def test_scope_rejects_unknown_site_and_preserves_schedule():
    faults.install("site.a@step=1")
    with pytest.raises(ValueError, match="unknown fault site"):
        with faults.scope("data.fetchh@step=1"):
            pass
    # The failed scope never touched the armed schedule.
    assert [s.site for s in faults.active()] == ["site.a"]
    assert faults.ARMED


def test_configure_warns_on_unknown_env_site(monkeypatch):
    # A typo'd FLUXMPI_TPU_FAULTS degrades with a warning naming the
    # nearest registered site — it must not crash init().
    monkeypatch.setenv("FLUXMPI_TPU_FAULTS", "comm.allredcue@step=1")
    with pytest.warns(UserWarning, match=r"comm\.allreduce"):
        specs = faults.configure()
    assert [s.site for s in specs] == ["comm.allredcue"]  # installed as asked


def test_register_site_extends_the_registry():
    site = faults.register_site("userlib.flush")
    assert site in faults.registered_sites()
    faults.install("userlib.flush@step=1")  # no raise: registered
    with pytest.raises(FaultInjectedError):
        faults.check("userlib.flush")


def test_armed_data_fetch_site_fires(world):
    ds = fm.ArrayDataset((np.arange(32, dtype=np.float32).reshape(32, 1),))
    loader = fm.DistributedDataLoader(ds, 8, mesh=world, prefetch=0)
    with faults.scope("data.fetch@step=3"):
        it = iter(loader)
        next(it)
        next(it)
        with pytest.raises(FaultInjectedError, match="data.fetch"):
            next(it)


# ---------------------------------------------------------------------------
# Crash-consistent checkpoints: commit protocol, quarantine, retries
# ---------------------------------------------------------------------------


def _state():
    return {"w": jnp.arange(4.0), "b": jnp.ones((2,))}


def test_ckpt_write_fault_exercises_retries(world, tmp_path, monkeypatch):
    from fluxmpi_tpu.utils import CheckpointManager, checkpoint as ckpt_mod

    sleeps = []
    monkeypatch.setattr(ckpt_mod, "_retry_sleep", sleeps.append)
    reg = MetricsRegistry()
    old = get_registry()
    set_registry(reg)
    try:
        mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
        with faults.scope("ckpt.write@step=1:times=2"):
            mgr.save(1, _state())  # two injected failures, then success
        assert mgr.all_steps() == [1]
        assert reg.counter("checkpoint.retries").value == 2
        # Capped exponential backoff, never slept for real.
        assert sleeps == [pytest.approx(0.1), pytest.approx(0.2)]
        _, restored = mgr.restore(_state())
        np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(4.0))
    finally:
        set_registry(old)


def test_ckpt_write_fault_exhausts_retries_and_raises(world, tmp_path, monkeypatch):
    from fluxmpi_tpu.utils import CheckpointManager, checkpoint as ckpt_mod

    monkeypatch.setattr(ckpt_mod, "_retry_sleep", lambda s: None)
    monkeypatch.setenv("FLUXMPI_TPU_CKPT_RETRIES", "1")
    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.scope("ckpt.write:p=1:seed=0"):  # every attempt fails
        with pytest.raises(FaultInjectedError, match="ckpt.write"):
            mgr.save(1, _state())
    # The failed save left nothing committed and nothing discoverable,
    # and the abort cleaned its own staging dir + peer-failure sentinel.
    assert mgr.latest_step() is None
    leftovers = [
        n
        for n in os.listdir(mgr.directory)
        if n.endswith(".tmp") or ".write_failed." in n
    ]
    assert leftovers == []


def test_peer_write_failure_aborts_save_everywhere(world, tmp_path, monkeypatch):
    """A peer process whose write exhausted retries (simulated via the
    monkeypatchable sentinel read) aborts the save on THIS healthy
    process too: staging cleaned, nothing decommitted, the previous
    committed checkpoint still restorable."""
    from fluxmpi_tpu.utils import CheckpointManager, checkpoint as ckpt_mod

    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    mgr.save(1, _state())
    monkeypatch.setattr(ckpt_mod, "_peer_write_failures", lambda tmp: [1])
    with pytest.raises(OSError, match=r"peer process\(es\) \[1\]"):
        mgr.save(2, _state())
    monkeypatch.undo()
    # Local write succeeded, but the save must not commit half a world:
    # step 2 is invisible, step 1 untouched, staging gone.
    assert mgr.all_steps() == [1]
    step, restored = mgr.restore(_state())
    assert step == 1
    leftovers = [n for n in os.listdir(mgr.directory) if n.endswith(".tmp")]
    assert leftovers == []


def test_crash_between_rename_and_commit_is_invisible(world, tmp_path):
    """A save that dies after the rename but before the COMMIT marker
    (the ckpt.commit site) must never be returned by discovery, and the
    next manager startup quarantines the partial directory."""
    from fluxmpi_tpu.utils import CheckpointManager

    d = str(tmp_path / "run")
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(1, _state())
    with faults.scope("ckpt.commit@step=1"):
        with pytest.raises(FaultInjectedError, match="ckpt.commit"):
            mgr.save(2, _state())
    # The torn step 2 is invisible: latest committed step is still 1.
    assert mgr.all_steps() == [1]
    assert mgr.latest_step() == 1
    step, restored = mgr.restore(_state())
    assert step == 1
    # Uncommitted dir is still on disk until the next startup sweep...
    assert os.path.isdir(os.path.join(d, "step_00000002"))
    with pytest.warns(UserWarning, match="quarantined"):
        mgr2 = CheckpointManager(d, async_save=False)
    assert mgr2.quarantined == ["step_00000002"]
    assert not os.path.isdir(os.path.join(d, "step_00000002"))
    assert os.path.isdir(os.path.join(d, "_quarantine", "step_00000002"))
    assert mgr2.all_steps() == [1]  # committed history untouched


def test_stale_tmp_dir_is_quarantined(world, tmp_path):
    from fluxmpi_tpu.utils import CheckpointManager

    d = tmp_path / "run"
    d.mkdir()
    (d / "step_00000003.tmp").mkdir()  # crash mid-write
    with pytest.warns(UserWarning, match="quarantined"):
        mgr = CheckpointManager(str(d), async_save=False)
    assert mgr.quarantined == ["step_00000003.tmp"]
    assert mgr.latest_step() is None


def test_save_overwrites_and_recommits(world, tmp_path):
    from fluxmpi_tpu.utils import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    mgr.save(1, _state())
    mgr.save(1, {"w": jnp.arange(4.0) + 10, "b": jnp.ones((2,))}, force=True)
    _, restored = mgr.restore(_state())
    np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(4.0) + 10)


def test_ckpt_read_fault_site(world, tmp_path):
    from fluxmpi_tpu.utils import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "run"), async_save=False)
    mgr.save(1, _state())
    with faults.scope("ckpt.read@step=1"):
        with pytest.raises(FaultInjectedError, match="ckpt.read"):
            mgr.restore(_state())
    mgr.restore(_state())  # transient: the next read succeeds


def test_step_desync_aborts_save_with_flight_context(world, tmp_path, monkeypatch):
    from fluxmpi_tpu.utils import CheckpointManager, checkpoint as ckpt_mod

    monkeypatch.setattr(
        ckpt_mod, "_gather_steps", lambda step: np.asarray([step, step + 1])
    )
    d = str(tmp_path / "run")
    mgr = CheckpointManager(d, async_save=False)
    with pytest.raises(CheckpointDesyncError, match="disagree"):
        mgr.save(5, _state())
    assert mgr.latest_step() is None  # nothing banked
    dump = os.path.join(d, "ckpt_desync_flight.0.json")
    assert os.path.exists(dump)
    with open(dump) as f:
        rec = json.load(f)
    assert rec["kind"] == "flight_recorder"


def test_wait_with_diagnostic_hard_deadline(monkeypatch):
    from concurrent.futures import Future

    from fluxmpi_tpu.utils.checkpoint import _wait_with_diagnostic

    fut: Future = Future()  # never completes
    monkeypatch.setenv("FLUXMPI_TPU_CKPT_TIMEOUT", "0.05")
    with pytest.raises(CheckpointTimeoutError, match="hard deadline"):
        with pytest.warns(UserWarning):
            _wait_with_diagnostic(fut, "test save", warn_after_s=0.01)
    # Default-off: unset env keeps the warn-forever contract (bounded
    # here by completing the future after the first warning window).
    monkeypatch.delenv("FLUXMPI_TPU_CKPT_TIMEOUT")
    done: Future = Future()
    done.set_result(None)
    _wait_with_diagnostic(done, "test save", warn_after_s=0.01)


def test_shutdown_resets_fault_plane(world):
    """shutdown() is the runtime reset: a fault schedule or preemption
    flag surviving an init/shutdown cycle would poison the next run
    (collectives raising FaultInjectedError, train_loop "preempting" at
    its first dispatch boundary)."""
    from fluxmpi_tpu import runtime

    saved = (runtime._state.initialized, runtime._state.mesh)
    try:
        faults.install("comm.allreduce:p=1:seed=0")
        runtime.install_preemption_handlers()
        runtime.request_preemption()
        runtime.shutdown()
        assert faults.active() == []
        assert not faults.ARMED
        assert not runtime.preemption_requested()
        assert not runtime.preemption_handlers_installed()
    finally:
        runtime.uninstall_preemption_handlers()
        runtime._state.initialized, runtime._state.mesh = saved


# ---------------------------------------------------------------------------
# Schema extensions (satellite: fault.injected / checkpoint.retries /
# train.resumes names + the preemption trace-event type)
# ---------------------------------------------------------------------------


def test_schema_knows_fault_tolerance_metrics():
    from fluxmpi_tpu.telemetry import schema

    for name in ("fault.injected", "checkpoint.retries", "train.resumes"):
        assert name in schema.KNOWN_METRIC_NAMES
        assert not schema.validate_metric(
            {"name": name, "type": "counter", "labels": {}, "value": 1}
        )
    # Drift inside a framework-owned namespace is an error...
    assert schema.validate_metric(
        {"name": "fault.bogus", "type": "counter", "labels": {}, "value": 1}
    )
    assert schema.validate_metric(
        {"name": "checkpoint.bogus", "type": "gauge", "labels": {}, "value": 1}
    )
    # ...while user-minted names elsewhere stay legal.
    assert not schema.validate_metric(
        {"name": "train.my_metric", "type": "gauge", "labels": {}, "value": 1}
    )


def test_schema_validates_preemption_trace_event():
    from fluxmpi_tpu.telemetry import schema

    good = {
        "name": schema.PREEMPTION_EVENT,
        "ph": "i",
        "ts": 1.0,
        "pid": 1,
        "tid": 1,
        "args": {"step": 12},
    }
    assert not schema.validate_trace_event(good)
    bad_phase = dict(good, ph="X", dur=1.0)
    assert any("instant" in e for e in schema.validate_trace_event(bad_phase))
    no_step = dict(good, args={})
    assert any("args.step" in e for e in schema.validate_trace_event(no_step))


def test_check_metrics_schema_script_accepts_fault_metrics(world, tmp_path):
    """End to end: a JSONL carrying the new counters passes the PR-time
    drift checker; a drifted name in a closed namespace fails it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_cms", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "check_metrics_schema.py",
        ),
    )
    cms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cms)
    schema = cms._load_schema()

    reg = MetricsRegistry()
    reg.counter("fault.injected", site="comm.allreduce").inc()
    reg.counter("checkpoint.retries").inc()
    reg.counter("train.resumes").inc()
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(reg.flush()) + "\n")
    assert cms.check_file(str(good), schema) == []

    bad_rec = reg.flush()
    bad_rec["metrics"].append(
        {"name": "fault.unknown", "type": "counter", "labels": {}, "value": 1}
    )
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(bad_rec) + "\n")
    assert cms.check_file(str(bad), schema)


# ---------------------------------------------------------------------------
# delay= entries: stall injection (the liveness-chaos producer)
# ---------------------------------------------------------------------------


def test_delay_modifier_grammar_round_trip():
    spec = faults.parse_spec("data.fetch@step=2:delay=0.05")
    assert spec.delay == pytest.approx(0.05)
    assert spec.step == 2
    assert "delay=0.05" in str(spec)
    # and the canonical string re-parses to the same schedule
    again = faults.parse_spec(str(spec))
    assert again.delay == spec.delay and again.step == spec.step


def test_delay_modifier_validation():
    with pytest.raises(ValueError):
        faults.parse_spec("data.fetch:delay=0")
    with pytest.raises(ValueError):
        faults.parse_spec("data.fetch:delay=-1")


def test_delay_entry_stalls_instead_of_raising():
    """A delay= entry is a STALL, not a crash: the firing hit sleeps in
    place and continues — no FaultInjectedError — while still counting
    as an injection (counter + trace instant ride the same path)."""
    import time as _time

    with faults.scope("data.fetch@step=2:delay=0.05"):
        t0 = _time.perf_counter()
        faults.check("data.fetch")  # hit 1: not yet
        fast = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        faults.check("data.fetch")  # hit 2: stalls, returns normally
        stalled = _time.perf_counter() - t0
        assert faults.injected_count() == 1
        t0 = _time.perf_counter()
        faults.check("data.fetch")  # times=1 default: spent
        spent = _time.perf_counter() - t0
    assert stalled >= 0.05
    assert fast < 0.04 and spent < 0.04
