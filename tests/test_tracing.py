"""Trace-plane tests: span ring + Chrome-trace export, the collective
flight recorder (ring wraparound, sequence monotonicity, cross-"host"
desync diffing), the hang watchdog (fake clock, zero real sleeps), the
schema checker's trace dispatch, and merge_traces.py.

The acceptance story: a simulated stall produces a dump file containing
thread stacks, the last-N collective ring with sequence numbers, and a
schema-valid final registry flush; merged per-host trace exports load as
valid Chrome-trace JSON.
"""

import json
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

from fluxmpi_tpu.telemetry import (
    FlightRecorder,
    JSONLSink,
    MemorySink,
    MetricsRegistry,
    TRACE_SCHEMA,
    Tracer,
    TrainingMonitor,
    Watchdog,
    diff_flight_dumps,
    get_flight_recorder,
    validate_flight_dump,
    validate_record,
    validate_trace_export,
    validate_watchdog_dump,
)
from fluxmpi_tpu.telemetry import tracing, watchdog as watchdog_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHECKER = os.path.join(_REPO, "scripts", "check_metrics_schema.py")
_MERGER = os.path.join(_REPO, "scripts", "merge_traces.py")


def _run_script(script, *args):
    return subprocess.run(
        [sys.executable, script, *args],
        capture_output=True,
        text=True,
        timeout=60,
    )


# ---------------------------------------------------------------------------
# Tracer: spans, nesting, ring bound, export round-trip
# ---------------------------------------------------------------------------


def test_span_nesting_and_export_round_trip(tmp_path):
    tr = Tracer(capacity=128, enabled=True)
    with tr.span("train.step", step=1):
        with tr.span("data.wait"):
            pass
        tr.instant("grad.ready", norm=1.5)
    record = tr.export(str(tmp_path / "trace.json"))
    assert validate_trace_export(record) == []

    # Round-trip: the written file is plain Chrome-trace JSON.
    loaded = json.loads((tmp_path / "trace.json").read_text())
    assert loaded["schema"] == TRACE_SCHEMA and loaded["kind"] == "trace"
    events = [e for e in loaded["traceEvents"] if e["ph"] != "M"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"train.step", "data.wait", "grad.ready"}
    # Nesting: the child "X" event lies within the parent's [ts, ts+dur].
    parent, child = by_name["train.step"], by_name["data.wait"]
    assert parent["ts"] <= child["ts"]
    # 2 µs slack: ts values are unix-epoch µs, where float64 rounding is
    # ~0.5 µs per operand.
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 2.0
    assert parent["args"] == {"step": 1}
    assert by_name["grad.ready"]["ph"] == "i"
    # Metadata rows make the Perfetto lanes readable.
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in loaded["traceEvents"])


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(50):
        tr.instant("tick", i=i)
    assert len(tr) == 8
    record = tr.export()
    ticks = [e for e in record["traceEvents"] if e["name"] == "tick"]
    assert [e["args"]["i"] for e in ticks] == list(range(42, 50))


def test_disabled_tracer_records_nothing_and_reuses_noop():
    tr = Tracer(capacity=8, enabled=False)
    cm1 = tr.span("a")
    cm2 = tr.span("b")
    assert cm1 is cm2  # shared no-op singleton: zero allocation per call
    with cm1:
        with cm2:
            tr.instant("x")
            tr.add_complete_event("y", 0.0, 1.0)
    assert len(tr) == 0
    assert tr.open_spans() == []


def test_open_spans_visible_while_active():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            stacks = tr.open_spans()
            assert len(stacks) == 1
            assert stacks[0]["thread_id"] == threading.get_ident()
            assert stacks[0]["spans"] == ["outer", "inner"]
    assert tr.open_spans() == []


def test_add_complete_event_lands_on_wall_clock_timeline():
    import time

    tr = Tracer(enabled=True)
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    tr.add_complete_event("comm.allreduce", t0, t1, path="device", nbytes=64)
    ev = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"][0]
    assert ev["dur"] == pytest.approx(0.25e6, rel=1e-3)  # microseconds
    # ts is unix-anchored: within a day of now, not a raw perf_counter.
    assert abs(ev["ts"] / 1e6 - time.time()) < 86400
    assert ev["args"] == {"path": "device", "nbytes": 64}


def test_configure_specs():
    prev = tracing.get_tracer()
    try:
        tr = Tracer(capacity=4)
        assert tracing.configure(tr) is tr
        assert tracing.get_tracer() is tr and tr.enabled
        tracing.configure(False)
        assert not tr.enabled
        tracing.configure(True)
        assert tr.enabled
        with pytest.raises(ValueError, match="trace spec"):
            tracing.configure(3.14)
        # A bad placeholder must fail HERE, not silently at shutdown.
        with pytest.raises(ValueError, match="not formattable"):
            tracing.configure("trace-{rank}.json")
    finally:
        tracing.set_tracer(prev)
        tracing._export_path = None


# ---------------------------------------------------------------------------
# Flight recorder: wraparound, monotonicity, comm wiring, dumps
# ---------------------------------------------------------------------------


def test_flight_ring_wraparound_and_seq_monotonicity():
    fr = FlightRecorder(capacity=8)
    for _ in range(20):
        fr.complete(fr.begin("allreduce", "device", 128))
    assert len(fr) == 8
    dump = fr.dump()
    assert validate_flight_dump(dump) == []
    seqs = [e["seq"] for e in dump["entries"]]
    assert seqs == list(range(13, 21))  # oldest fell off; order preserved
    assert dump["sequence"] == 20 and dump["completed"] == 20
    assert all(e["completed"] for e in dump["entries"])


def test_flight_in_flight_entry_marks_the_hang():
    fr = FlightRecorder(capacity=4)
    fr.complete(fr.begin("allreduce", "device", 64))
    fr.begin("bcast", "device", 256)  # never completes: the "hang"
    dump = fr.dump()
    assert validate_flight_dump(dump) == []
    tail = dump["entries"][-1]
    assert tail["completed"] is False and tail["duration"] is None
    assert tail["op"] == "bcast"
    assert fr.completed_count == 1


def test_comm_collectives_feed_the_flight_recorder(world, nworkers):
    import fluxmpi_tpu as fm

    fr = get_flight_recorder()
    seq0, done0 = fr.sequence, fr.completed_count
    x = np.ones((nworkers, 2), dtype=np.float32)
    fm.allreduce(x)
    fm.bcast(x, root=0)
    fm.host_allgather(np.float32(1.0))
    assert fr.sequence == seq0 + 3
    assert fr.completed_count == done0 + 3
    ops = [e.op for e in fr.entries()[-3:]]
    assert ops == ["allreduce", "bcast", "host_allgather"]
    tail = fr.entries()[-1]
    assert tail.completed and tail.path == "host"


def test_raised_collective_aborts_entry_instead_of_faking_a_hang(world):
    import fluxmpi_tpu as fm

    fr = get_flight_recorder()
    with pytest.raises(ValueError, match="root rank"):
        fm.bcast(np.ones((8, 2), dtype=np.float32), root=99)
    # Root range is validated before _begin_op, so nothing recorded; an
    # error INSIDE the collective call must finalize the entry as
    # aborted, not leave it "in flight" forever. Exercise via abort().
    entry = fr.begin("allreduce", "device", 64)
    fr.abort(entry)
    dump = fr.dump()
    tail = dump["entries"][-1]
    assert tail["completed"] is True and tail["aborted"] is True
    assert validate_flight_dump(dump) == []  # extra key tolerated
    # Aborts are not progress: completed_count untouched.
    assert not any(
        e["seq"] == entry.seq for d in [dump]
        for e in d["entries"] if not e["completed"]
    )


def test_cross_host_desync_diff():
    # Two in-memory "hosts": host 0 completed 10 collectives, host 1
    # hangs inside seq 9 — the diff names the stuck collective.
    h0, h1 = FlightRecorder(capacity=16), FlightRecorder(capacity=16)
    for i in range(10):
        h0.complete(h0.begin("allreduce", "device", 1024))
    for i in range(8):
        h1.complete(h1.begin("allreduce", "device", 1024))
    h1.begin("allreduce", "device", 1024)  # in flight: the hang
    d0, d1 = h0.dump(), h1.dump()
    d1["process"] = 1
    diff = diff_flight_dumps([d0, d1])
    assert diff["max_sequence"] == 10 and diff["min_sequence"] == 9
    assert diff["laggards"] == [1]
    assert diff["hosts"]["1"]["in_flight"]["seq"] == 9
    assert diff["hosts"]["1"]["in_flight"]["op"] == "allreduce"
    assert diff["hosts"]["0"]["in_flight"] is None
    assert diff["first_mismatch"] is None  # lag, not divergence
    assert diff["synchronized"] is False


def test_cross_host_divergence_diff_finds_first_mismatch():
    # Hosts disagree on what collective seq 3 *is* — a divergence bug
    # (mismatched program order), distinct from a mere lag.
    h0, h1 = FlightRecorder(capacity=16), FlightRecorder(capacity=16)
    for op0, op1 in [("allreduce", "allreduce"), ("bcast", "bcast"),
                     ("allreduce", "reduce"), ("barrier", "barrier")]:
        h0.complete(h0.begin(op0, "device", 64))
        h1.complete(h1.begin(op1, "device", 64))
    d0, d1 = h0.dump(), h1.dump()
    d1["process"] = 1
    diff = diff_flight_dumps([d0, d1])
    assert diff["first_mismatch"]["seq"] == 3
    assert diff["first_mismatch"]["entries"]["0"]["op"] == "allreduce"
    assert diff["first_mismatch"]["entries"]["1"]["op"] == "reduce"
    assert diff["synchronized"] is False


def test_healthy_hosts_diff_synchronized():
    h0, h1 = FlightRecorder(), FlightRecorder()
    for _ in range(5):
        h0.complete(h0.begin("allreduce", "device", 64))
        h1.complete(h1.begin("allreduce", "device", 64))
    d0, d1 = h0.dump(), h1.dump()
    d1["process"] = 1
    diff = diff_flight_dumps([d0, d1])
    assert diff["synchronized"] is True
    assert diff["laggards"] == [] and diff["first_mismatch"] is None


def test_diff_rejects_duplicate_process_indices():
    h0, h1 = FlightRecorder(), FlightRecorder()
    h0.complete(h0.begin("allreduce", "device", 64))
    h1.begin("bcast", "device", 64)
    # Both dumps stamp process 0 (pre-init): collapsing them could call
    # a desynced pair synchronized — must refuse instead.
    with pytest.raises(ValueError, match="share process index"):
        diff_flight_dumps([h0.dump(), h1.dump()])


# ---------------------------------------------------------------------------
# Watchdog: fake clock, no real sleeps
# ---------------------------------------------------------------------------


def _fake_watchdog(tmp_path, **kwargs):
    clock = {"t": 0.0}
    progress = {"n": 0}
    wd = Watchdog(
        deadline=30.0,
        dump_dir=str(tmp_path),
        sources=[lambda: progress["n"]],
        clock=lambda: clock["t"],
        **kwargs,
    )
    return wd, clock, progress


def test_watchdog_dumps_on_simulated_stall(tmp_path):
    mem = MemorySink()
    reg = MetricsRegistry(sinks=[mem])
    reg.counter("train.steps").inc(7)
    tr = Tracer(enabled=True)
    fr = FlightRecorder(capacity=8)
    for _ in range(3):
        fr.complete(fr.begin("allreduce", "device", 4096))
    fr.begin("bcast", "device", 128)  # the collective "we" hang in
    wd, clock, progress = _fake_watchdog(tmp_path)
    wd._registry, wd._tracer, wd._recorder = reg, tr, fr

    span_cm = tr.span("train.step")
    span_cm.__enter__()  # a live span when the stall fires
    try:
        assert wd.check() is None  # seeds the baseline at t=0
        clock["t"] = 10.0
        progress["n"] += 1
        assert wd.check() is None  # progress observed: timer resets
        clock["t"] = 35.0
        assert wd.check() is None  # only 25 s since last progress
        clock["t"] = 41.0
        path = wd.check()  # 31 s stalled: dump
        assert path is not None and os.path.exists(path)
        assert wd.check() is None  # one dump per plateau
        dump = json.load(open(path, encoding="utf-8"))
    finally:
        span_cm.__exit__(None, None, None)

    assert validate_watchdog_dump(dump) == []
    assert dump["reason"] == "stall"
    # Thread stacks: this test's own frame is in the dump.
    me = [t for t in dump["threads"]
          if t["thread_id"] == threading.get_ident()]
    assert me and any(
        fr_["function"] == "test_watchdog_dumps_on_simulated_stall"
        for fr_ in me[0]["stack"]
    )
    # Flight-recorder tail with sequence numbers, in-flight op visible:
    entries = dump["flight_recorder"]["entries"]
    assert [e["seq"] for e in entries] == [1, 2, 3, 4]
    assert entries[-1]["op"] == "bcast" and not entries[-1]["completed"]
    # Open span stack:
    assert dump["open_spans"] == [
        {"thread_id": threading.get_ident(), "spans": ["train.step"]}
    ]
    # Final registry flush: schema-valid and actually written to sinks.
    assert validate_record(dump["registry_flush"]) == []
    assert dump["registry_flush"]["watchdog_reason"] == "stall"
    assert len(mem.records) == 1
    # The documented validator accepts the artifact.
    proc = _run_script(_CHECKER, path)
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_watchdog_redump_after_progress_resumes(tmp_path):
    wd, clock, progress = _fake_watchdog(tmp_path)
    wd._registry = MetricsRegistry()
    assert wd.check() is None
    clock["t"] = 31.0
    assert wd.check() is not None  # first stall
    clock["t"] = 40.0
    progress["n"] += 1
    assert wd.check() is None  # recovery observed
    clock["t"] = 75.0
    assert wd.check() is not None  # a second stall dumps again


def test_watchdog_signal_dump(tmp_path):
    import time

    # The handler must not dump inline (a signal handler taking the
    # registry lock on the main thread can self-deadlock): it sets a
    # flag the armed daemon thread serves on its next sub-tick.
    wd, clock, progress = _fake_watchdog(tmp_path, poll_interval=0.01)
    wd._registry = MetricsRegistry()
    try:
        wd.arm(install_signal=False)
        wd._on_sigusr1(signal.SIGUSR1, None)
        assert wd._signal_requested or wd.last_dump_path  # flag, not dump
        deadline = time.monotonic() + 10.0
        while wd.last_dump_path is None and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        wd.disarm()
    assert wd.last_dump_path is not None
    dump = json.load(open(wd.last_dump_path, encoding="utf-8"))
    assert validate_watchdog_dump(dump) == []
    assert dump["reason"] == "signal"


def test_watchdog_arm_disarm_thread_and_module_wiring(tmp_path):
    wd, clock, progress = _fake_watchdog(tmp_path, poll_interval=0.01)
    wd._registry = MetricsRegistry()
    try:
        armed = watchdog_mod.arm_watchdog(wd)
        assert armed is wd and wd.armed
        assert watchdog_mod.get_watchdog() is wd
        # configure() replay with the same armed instance is a no-op.
        assert watchdog_mod.configure(wd) is wd
    finally:
        watchdog_mod.disarm_watchdog()
    assert not wd.armed and watchdog_mod.get_watchdog() is None


def test_watchdog_configure_specs(tmp_path, monkeypatch):
    monkeypatch.setenv("FLUXMPI_TPU_WATCHDOG_DIR", str(tmp_path))
    try:
        wd = watchdog_mod.configure("45")
        assert wd is not None and wd.deadline == 45.0 and wd.armed
        assert wd.dump_dir == str(tmp_path)
        assert watchdog_mod.configure("45") is wd  # idempotent replay
        with pytest.raises(ValueError, match="watchdog spec"):
            watchdog_mod.configure("not-a-number")
        assert watchdog_mod.configure("0") is None
        assert watchdog_mod.get_watchdog() is None
    finally:
        watchdog_mod.disarm_watchdog()


def test_notify_progress_and_default_sources():
    before = watchdog_mod._progress
    watchdog_mod.notify_progress()
    watchdog_mod.notify_progress(3)
    assert watchdog_mod._progress == before + 4


def test_monitor_progress_shares_heartbeat_truth():
    reg = MetricsRegistry()
    mon = TrainingMonitor(registry=reg, interval=1, cross_host=False)
    assert mon.progress == 0
    g0 = watchdog_mod._progress
    mon.collect()
    mon.collect()
    # One source of truth: progress IS the heartbeat counter...
    assert mon.progress == 2
    assert reg.counter("monitor.heartbeat").value == 2
    # ...and each collect also ticks the armed-watchdog global source.
    assert watchdog_mod._progress == g0 + 2


# ---------------------------------------------------------------------------
# Wiring: train-step spans, runtime kwargs, shutdown export
# ---------------------------------------------------------------------------


def test_train_step_emits_span_and_progress(world):
    import optax

    import jax
    import jax.numpy as jnp
    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model = MLP(features=(4, 1))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 2)))
    optimizer = optax.sgd(0.1)

    def loss_fn(p, mstate, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2), mstate

    tr = Tracer(enabled=True)
    prev = tracing.set_tracer(tr)
    g0 = watchdog_mod._progress
    try:
        step = make_train_step(
            loss_fn, optimizer, donate=False, metrics=MetricsRegistry()
        )
        st = replicate(TrainState.create(params, optimizer))
        batch = shard_batch((
            np.ones((8, 2), dtype=np.float32),
            np.ones((8, 1), dtype=np.float32),
        ))
        for _ in range(2):
            st, _ = step(st, batch)
    finally:
        tracing.set_tracer(prev)
    steps = [e for e in tr.export()["traceEvents"]
             if e["name"] == "train.step"]
    assert len(steps) == 2 and all(e["dur"] > 0 for e in steps)
    assert watchdog_mod._progress == g0 + 2  # liveness per step


def test_loader_emits_fetch_events(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    tr = Tracer(enabled=True)
    prev = tracing.set_tracer(tr)
    try:
        data = ArrayDataset(np.arange(64, dtype=np.float32).reshape(32, 2))
        batches = list(DistributedDataLoader(data, 8, prefetch=0))
    finally:
        tracing.set_tracer(prev)
    fetches = [e for e in tr.export()["traceEvents"]
               if e["name"] == "data.fetch"]
    assert len(fetches) == len(batches) == 4
    assert [e["args"]["batch"] for e in fetches] == [0, 1, 2, 3]


def test_init_wires_trace_and_watchdog_kwargs(world, tmp_path):
    import fluxmpi_tpu as fm

    prev = tracing.get_tracer()
    prev_enabled = prev.enabled
    try:
        fm.init(trace=True, watchdog=60)
        assert tracing.get_tracer().enabled
        wd = watchdog_mod.get_watchdog()
        assert wd is not None and wd.armed and wd.deadline == 60.0
    finally:
        watchdog_mod.disarm_watchdog()
        prev.enabled = prev_enabled


def test_tracing_shutdown_exports_configured_path(tmp_path):
    prev = tracing.get_tracer()
    tr = Tracer(enabled=True)
    tracing.set_tracer(tr)
    try:
        path = str(tmp_path / "trace.{process}.json")
        tracing.configure(path)
        tr.instant("mark")
        written = tracing.shutdown()
        assert written == str(tmp_path / "trace.0.json")
        loaded = json.load(open(written, encoding="utf-8"))
        assert validate_trace_export(loaded) == []
    finally:
        tracing.set_tracer(prev)
        tracing._export_path = None


# ---------------------------------------------------------------------------
# Scripts: schema checker dispatch + merge_traces
# ---------------------------------------------------------------------------


def test_checker_validates_trace_plane_files(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("s"):
        pass
    trace_path = tmp_path / "trace.json"
    tr.export(str(trace_path))

    fr = FlightRecorder()
    fr.complete(fr.begin("allreduce", "device", 64))
    flight_path = tmp_path / "flight.json"
    flight_path.write_text(json.dumps(fr.dump()))

    proc = _run_script(_CHECKER, str(trace_path), str(flight_path))
    assert proc.returncode == 0, proc.stderr + proc.stdout

    bad = tmp_path / "bad_trace.json"
    bad.write_text(json.dumps({
        "schema": TRACE_SCHEMA, "kind": "trace", "time_unix": 1.0,
        "process": 0,
        "traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}],  # no dur
    }))
    proc = _run_script(_CHECKER, str(bad))
    assert proc.returncode == 1 and "dur" in proc.stderr


def test_merge_traces_produces_loadable_chrome_trace(tmp_path):
    paths = []
    for process in (0, 1):
        tr = Tracer(enabled=True)
        with tr.span("train.step", host=process):
            pass
        rec = tr.export()
        rec["process"] = process  # simulate per-host exports
        for ev in rec["traceEvents"]:
            if ev.get("name") == "process_name":
                ev["args"] = {"name": f"host {process}"}
        p = tmp_path / f"trace.{process}.json"
        p.write_text(json.dumps(rec))
        paths.append(str(p))

    out = str(tmp_path / "merged.json")
    proc = _run_script(_MERGER, "-o", out, *paths)
    assert proc.returncode == 0, proc.stderr + proc.stdout

    merged = json.load(open(out, encoding="utf-8"))
    # Valid Chrome-trace JSON: a traceEvents list of well-formed events —
    # exactly what Perfetto/chrome://tracing loads — and still valid
    # against our schema (extra keys are Chrome-trace metadata).
    assert validate_trace_export(merged) == []
    assert merged["merged_from"] == [0, 1]
    spans = [e for e in merged["traceEvents"] if e["name"] == "train.step"]
    assert len(spans) == 2
    # Events are re-pidded to the host's process index: the two hosts
    # here share one real pid (same test process), which would
    # otherwise fold both into one Perfetto lane.
    assert sorted(e["pid"] for e in spans) == [0, 1]
    names = {json.dumps(e["args"]) for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {'{"name": "host 0"}', '{"name": "host 1"}'}
    # The merged file validates through the checker too.
    proc = _run_script(_CHECKER, out)
    assert proc.returncode == 0, proc.stderr + proc.stdout


# ---------------------------------------------------------------------------
# Satellites: step_timer sentinel cache, profile_trace
# ---------------------------------------------------------------------------


def test_step_timer_sentinel_is_cached(world):
    from fluxmpi_tpu.utils import profiling

    holder = {}
    with profiling.step_timer(holder):
        pass  # nothing watched: the sentinel drain path runs
    first = profiling._sentinel_bump
    assert first is not None
    with profiling.step_timer(holder):
        pass
    # Same jitted callable both times — no per-call jit cache entry, so
    # timed no-watch steps stop retracing every call.
    assert profiling._sentinel_bump is first
    assert profiling._bump_fn() is first
    assert holder["seconds"] > 0


def test_profile_trace_lead_only_and_all_hosts(world, tmp_path, monkeypatch):
    from fluxmpi_tpu.utils import profiling

    calls = []

    class _FakeTrace:
        def __init__(self, logdir, **kwargs):
            calls.append((logdir, kwargs))
            os.makedirs(logdir, exist_ok=True)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    import jax

    monkeypatch.setattr(jax.profiler, "trace", _FakeTrace)
    # Default: lead process traces (single-process world: that's us).
    with profiling.profile_trace(str(tmp_path / "a")):
        pass
    assert calls == [(str(tmp_path / "a"), {})]
    # all_hosts=True also traces here; profiler options pass through.
    with profiling.profile_trace(
        str(tmp_path / "b"), all_hosts=True, profiler_options="opts"
    ):
        pass
    assert calls[1] == (str(tmp_path / "b"), {"profiler_options": "opts"})
    # The span ring's export lies beside each capture.
    for sub in ("a", "b"):
        assert (tmp_path / sub / profiling.SPANS_FILE).exists()
    # The deprecated host_only= spelling is gone.
    with pytest.raises(TypeError):
        with profiling.profile_trace(str(tmp_path / "c"), host_only=True):
            pass


def test_merge_traces_discovers_proc_subdirectories(tmp_path):
    """A directory input is walked recursively — including the
    per-process proc<k> subdirectories profile_trace(all_hosts=True)
    and the AutoProfiler write into a shared logdir — with tolerant
    handling: our exports merge as usual, a raw Chrome trace from
    profiler tooling (.trace.json.gz) is wrapped with its process
    inferred from the proc<k> component, junk JSON is skipped."""
    import gzip

    logdir = tmp_path / "captures"
    (logdir / "proc1" / "plugins" / "profile" / "r1").mkdir(parents=True)
    tr = Tracer(enabled=True)
    with tr.span("train.step"):
        pass
    (logdir / "trace.0.json").write_text(json.dumps(tr.export()))
    raw = {"traceEvents": [
        {"name": "xla_op", "ph": "X", "ts": 5.0, "dur": 2.0,
         "pid": 7, "tid": 7},
    ]}
    with gzip.open(
        logdir / "proc1" / "plugins" / "profile" / "r1"
        / "host.trace.json.gz", "wt", encoding="utf-8"
    ) as f:
        json.dump(raw, f)
    (logdir / "proc1" / "notes.json").write_text('{"not": "a trace"}')

    out = str(tmp_path / "merged.json")
    proc = _run_script(_MERGER, "-o", out, str(logdir))
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "skipped" in proc.stdout  # the junk file, counted not fatal
    merged = json.load(open(out, encoding="utf-8"))
    assert validate_trace_export(merged) == []
    assert merged["merged_from"] == [0, 1]
    xla = [e for e in merged["traceEvents"] if e["name"] == "xla_op"]
    assert xla and xla[0]["pid"] == 1  # process inferred from proc1/
    spans = [e for e in merged["traceEvents"] if e["name"] == "train.step"]
    assert spans and spans[0]["pid"] == 0
    # An explicitly-named invalid file still errors (strict path kept).
    proc = _run_script(
        _MERGER, "-o", out, str(logdir / "proc1" / "notes.json")
    )
    assert proc.returncode == 1
