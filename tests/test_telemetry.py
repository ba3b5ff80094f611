"""Telemetry subsystem tests: registry semantics, sink round-trips, comm
instrumentation over the 8-device CPU mesh, the train-step metrics hook,
the TrainingMonitor, and the JSONL schema checker.

The acceptance loop at the bottom is the PR's contract: a CPU-only
training loop with the metrics hook enabled must produce a JSONL stream
carrying step time, examples/sec, loss, grad-norm, per-collective
byte/call counters, and memory stats — validated by
scripts/check_metrics_schema.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from fluxmpi_tpu.telemetry import (
    ConsoleSink,
    JSONLSink,
    MemorySink,
    MetricsRegistry,
    NullSink,
    SCHEMA,
    TrainingMonitor,
    configure,
    get_registry,
    validate_record,
)
from fluxmpi_tpu.telemetry import schema

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHECKER = os.path.join(_REPO, "scripts", "check_metrics_schema.py")


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("t.calls")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("t.depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0

    h = reg.histogram("t.lat")
    for v in (0.5, 1.5, 1.0):
        h.observe(v)
    assert h.count == 3
    assert h.sum == pytest.approx(3.0)
    assert h.min == 0.5 and h.max == 1.5 and h.last == 1.0
    assert h.mean == pytest.approx(1.0)
    # No schema-declared edges for this name: bucket-free summary.
    assert h.bins is None
    assert "buckets" not in h.snapshot()


def test_histogram_schema_declared_buckets():
    """Names with edges in schema.HISTOGRAM_BUCKET_EDGES bin into
    cumulative Prometheus-shaped buckets; the snapshot validates and an
    over-the-top observation counts only toward the implicit +Inf."""
    from fluxmpi_tpu.telemetry.schema import HISTOGRAM_BUCKET_EDGES

    reg = MetricsRegistry()
    h = reg.histogram("train.step_seconds")
    edges = HISTOGRAM_BUCKET_EDGES["train.step_seconds"]
    assert tuple(h.edges) == edges
    h.observe(0.003)   # lands in the le=0.005 bin
    h.observe(0.003)
    h.observe(0.3)     # le=0.5
    h.observe(1e9)     # beyond the last edge: +Inf only
    snap = h.snapshot()
    buckets = snap["buckets"]
    assert buckets["edges"] == list(edges)
    cum = dict(zip(buckets["edges"], buckets["counts"]))
    assert cum[0.0025] == 0
    assert cum[0.005] == 2
    assert cum[0.25] == 2
    assert cum[0.5] == 3
    assert cum[edges[-1]] == 3  # the 1e9 sample is only in count (+Inf)
    assert snap["count"] == 4
    # Cumulative counts are non-decreasing and the metric validates.
    assert buckets["counts"] == sorted(buckets["counts"])
    assert schema.validate_metric(snap) == []
    # A flush record carrying buckets stays schema-clean end to end.
    assert schema.validate_record(reg.flush()) == []
    # Corrupt bucket shapes are rejected.
    bad = dict(snap)
    bad["buckets"] = {"edges": [2.0, 1.0], "counts": [1, 0]}
    errs = schema.validate_metric(bad)
    assert any("strictly increasing" in e for e in errs)
    assert any("cumulative" in e for e in errs)


def test_labels_key_identity_and_separation():
    reg = MetricsRegistry()
    a = reg.counter("c.bytes", op="allreduce", path="device")
    # Same name+labels (any kwarg order, any stringable value) → same object.
    assert reg.counter("c.bytes", path="device", op="allreduce") is a
    b = reg.counter("c.bytes", op="bcast", path="device")
    assert b is not a
    a.inc(10)
    assert b.value == 0


def test_kind_conflict_and_empty_name_rejected():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    # One name, one kind holds ACROSS label sets too — otherwise a flush
    # line could carry the same name as two instrument types.
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("x", op="other")
    with pytest.raises(ValueError, match="non-empty"):
        reg.counter("")


def test_snapshot_shapes_validate_against_schema():
    reg = MetricsRegistry()
    reg.counter("a", op="x").inc()
    reg.gauge("b").set(1.0)
    reg.histogram("c").observe(0.1)
    reg.histogram("d")  # empty histogram: count 0, no stats keys
    record = reg.flush()
    assert record["schema"] == SCHEMA
    assert validate_record(record) == []
    empty = [m for m in record["metrics"] if m["name"] == "d"][0]
    assert empty["count"] == 0 and "mean" not in empty


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry(sinks=[JSONLSink(path)])
    reg.counter("io.calls", op="allreduce").inc(3)
    reg.histogram("io.lat").observe(0.25)
    reg.flush()
    reg.gauge("io.depth").set(2)
    reg.flush(extra_field="ok")

    lines = [
        json.loads(ln)
        for ln in open(path, encoding="utf-8").read().splitlines()
    ]
    assert len(lines) == 2
    for rec in lines:
        assert validate_record(rec) == []
    by_name = {m["name"]: m for m in lines[1]["metrics"]}
    assert by_name["io.calls"]["value"] == 3
    assert by_name["io.calls"]["labels"] == {"op": "allreduce"}
    assert by_name["io.lat"]["count"] == 1
    assert by_name["io.depth"]["value"] == 2.0
    assert lines[1]["extra_field"] == "ok"


def test_jsonl_sink_private_stream_keeps_fast_path(tmp_path):
    # The sink's one mode: persistent handle, no .lock sidecar.
    path = str(tmp_path / "private.jsonl")
    sink = JSONLSink(path)
    sink.write({"a": 1})
    sink.write({"a": 2})
    assert not os.path.exists(path + ".lock")
    sink.close()
    assert [json.loads(l)["a"] for l in open(path)] == [1, 2]


def test_memory_and_null_sinks_and_close():
    mem = MemorySink()
    reg = MetricsRegistry(sinks=[mem, NullSink()])
    reg.counter("m").inc()
    reg.flush()
    assert len(mem.records) == 1
    reg.close()  # flushes once more, then detaches
    assert len(mem.records) == 2
    assert reg.sinks == ()


def test_close_without_flush_writes_no_extra_line():
    mem = MemorySink()
    reg = MetricsRegistry(sinks=[mem])
    reg.counter("m").inc()
    reg.flush()
    reg.close(flush=False)
    assert len(mem.records) == 1
    assert reg.sinks == ()


def test_console_sink_prints_on_lead(capsys):
    reg = MetricsRegistry(sinks=[ConsoleSink()])
    reg.gauge("loss").set(0.125)
    reg.histogram("lat").observe(0.5)
    reg.flush()
    out = capsys.readouterr().out
    assert "telemetry:" in out and "loss=0.125" in out and "lat" in out


def test_configure_is_idempotent(tmp_path):
    path = str(tmp_path / "cfg.jsonl")
    before = len(get_registry().sinks)
    try:
        configure(path)
        configure(path)  # same path again — idempotent init() replay
        assert len(get_registry().sinks) == before + 1
    finally:
        for s in list(get_registry().sinks):
            if isinstance(s, JSONLSink) and s.path == path:
                get_registry().remove_sink(s)


# ---------------------------------------------------------------------------
# Comm instrumentation (real XLA collectives over the 8-device CPU mesh)
# ---------------------------------------------------------------------------


def _comm_metric(name, op, path="device"):
    reg = get_registry()
    if name == "comm.block_seconds":
        return reg.histogram(name, op=op, path=path)
    return reg.counter(name, op=op, path=path)


def test_allreduce_records_calls_bytes_and_time(world, nworkers):
    import fluxmpi_tpu as fm

    x = np.arange(nworkers * 4, dtype=np.float32).reshape(nworkers, 4)
    calls0 = _comm_metric("comm.calls", "allreduce").value
    bytes0 = _comm_metric("comm.bytes", "allreduce").value
    n0 = _comm_metric("comm.block_seconds", "allreduce").count

    out = fm.allreduce(x, op="sum")
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(x.sum(0), x.shape)
    )
    assert _comm_metric("comm.calls", "allreduce").value == calls0 + 1
    assert _comm_metric("comm.bytes", "allreduce").value == bytes0 + x.nbytes
    hist = _comm_metric("comm.block_seconds", "allreduce")
    assert hist.count == n0 + 1 and hist.last >= 0


def test_bcast_and_host_collectives_record(world, nworkers):
    import fluxmpi_tpu as fm

    # float32: a float64 host input stages to f32 (x64 disabled), and the
    # recorded bytes are the staged payload that actually moved.
    x = np.ones((nworkers, 2), dtype=np.float32)
    calls0 = _comm_metric("comm.calls", "bcast").value
    bytes0 = _comm_metric("comm.bytes", "bcast").value
    fm.bcast(x, root=1)
    assert _comm_metric("comm.calls", "bcast").value == calls0 + 1
    assert _comm_metric("comm.bytes", "bcast").value == bytes0 + x.nbytes

    h0 = _comm_metric("comm.calls", "host_allreduce", "host").value
    fm.host_allreduce(np.float32(2.0))
    assert _comm_metric("comm.calls", "host_allreduce", "host").value == h0 + 1

    g0 = _comm_metric("comm.calls", "host_allgather", "host").value
    gathered = fm.host_allgather(np.float32(3.0))
    assert gathered.shape == (1,) and gathered[0] == 3.0
    assert _comm_metric("comm.calls", "host_allgather", "host").value == g0 + 1


# ---------------------------------------------------------------------------
# Train-step metrics hook
# ---------------------------------------------------------------------------


def _mlp_problem():
    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import TrainState

    model = MLP(features=(8, 8, 1))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 2)))
    optimizer = optax.sgd(0.1)
    state = TrainState.create(params, optimizer)

    def loss_fn(p, mstate, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2), mstate

    rng = np.random.default_rng(0)
    batch = (
        rng.normal(size=(16, 2)).astype(np.float32),
        rng.normal(size=(16, 1)).astype(np.float32),
    )
    return loss_fn, optimizer, state, batch


@pytest.mark.parametrize("style", ["auto", "shard_map"])
def test_train_step_metrics_hook(world, style):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    loss_fn, optimizer, state, batch = _mlp_problem()
    reg = MetricsRegistry()
    step = make_train_step(
        loss_fn, optimizer, style=style, donate=False, metrics=reg
    )
    st = replicate(state)
    data = shard_batch(batch)
    for _ in range(3):
        st, loss = step(st, data)
    assert np.isfinite(float(loss))

    assert reg.counter("train.steps").value == 3
    assert reg.counter("train.examples").value == 3 * 16
    assert reg.histogram("train.step_seconds").count == 3
    assert reg.histogram("train.step_seconds").min > 0
    assert np.isfinite(reg.gauge("train.loss").value)
    assert np.isfinite(reg.gauge("train.grad_norm").value)
    assert reg.gauge("train.grad_norm").value > 0
    assert reg.gauge("train.examples_per_sec").value > 0
    assert int(st.step) == 3  # public signature unchanged


def test_train_step_metrics_callable_hook(world):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    loss_fn, optimizer, state, batch = _mlp_problem()
    records = []
    step = make_train_step(
        loss_fn, optimizer, donate=False, metrics=records.append
    )
    st, loss = step(replicate(state), shard_batch(batch))
    assert len(records) == 1
    rec = records[0]
    assert set(rec) == {
        "step_seconds", "loss", "grad_norm", "examples",
        "examples_per_sec", "steps",
    }
    assert rec["examples"] == 16 and rec["steps"] == 1
    assert rec["loss"] == pytest.approx(float(loss))
    assert np.isfinite(rec["grad_norm"]) and rec["step_seconds"] > 0


def test_train_step_metrics_with_scan_steps(world):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch
    from fluxmpi_tpu import config as fm_config
    from jax.sharding import PartitionSpec as P

    loss_fn, optimizer, state, batch = _mlp_problem()
    reg = MetricsRegistry()
    k = 2
    step = make_train_step(
        loss_fn, optimizer, donate=False, scan_steps=k, metrics=reg
    )
    stacked = jax.tree_util.tree_map(
        lambda a: np.broadcast_to(a, (k, *a.shape)), batch
    )
    data = shard_batch(stacked, spec=P(None, fm_config.DP_AXIS_NAME))
    st, losses = step(replicate(state), data)
    assert losses.shape == (k,)
    assert reg.counter("train.steps").value == k
    assert reg.counter("train.examples").value == k * 16
    assert np.isfinite(reg.gauge("train.grad_norm").value)


def test_train_step_rejects_bad_metrics_spec(world):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    loss_fn, optimizer, state, batch = _mlp_problem()
    with pytest.raises(ValueError, match="metrics"):
        make_train_step(loss_fn, optimizer, metrics=123)
    # False is off, same as None — a bool toggle flag must just work.
    step = make_train_step(loss_fn, optimizer, donate=False, metrics=False)
    st, loss = step(replicate(state), shard_batch(batch))
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# TrainingMonitor
# ---------------------------------------------------------------------------


def test_monitor_collects_on_interval_and_flags_stragglers(world):
    mem = MemorySink()
    reg = MetricsRegistry(sinks=[mem])
    mon = TrainingMonitor(registry=reg, interval=3, cross_host=False)
    assert mon.observe_step(0.01) is None
    assert mon.observe_step(0.01) is None
    summary = mon.observe_step(0.01)
    assert summary is not None
    assert summary["step_seconds_mean"] == pytest.approx(0.01)
    assert summary["straggler"] is False
    assert len(mem.records) == 1
    names = {m["name"] for m in mem.records[0]["metrics"]}
    assert "monitor.heartbeat" in names
    assert "monitor.step_seconds_mean" in names
    assert "host.memory.peak_rss_bytes" in names
    assert validate_record(mem.records[0]) == []
    # Single-host: max == mean, so straggler can never flag here; the
    # threshold math is pure python — exercise it directly.
    assert reg.gauge("monitor.straggler").value == 0.0


def test_monitor_heartbeat_advances_per_collect(world):
    reg = MetricsRegistry()
    mon = TrainingMonitor(registry=reg, interval=1, cross_host=False)
    mon.collect()
    t1 = reg.gauge("monitor.heartbeat_unix").value
    mon.collect()
    assert reg.counter("monitor.heartbeat").value == 2
    assert reg.gauge("monitor.heartbeat_unix").value >= t1


# ---------------------------------------------------------------------------
# Data loader instrumentation + transform_with_rng
# ---------------------------------------------------------------------------


def test_loader_records_fetch_latency_and_depth(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    reg = get_registry()
    n0 = reg.histogram("data.batch_fetch_seconds").count
    data = ArrayDataset(np.arange(64, dtype=np.float32).reshape(32, 2))
    loader = DistributedDataLoader(data, 8, prefetch=2)
    batches = list(loader)
    assert len(batches) == 4
    assert reg.histogram("data.batch_fetch_seconds").count == n0 + 4
    assert reg.gauge("data.prefetch_depth").value >= 0


def test_transform_with_rng_explicit_override(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    data = ArrayDataset(np.ones((16, 2), dtype=np.float32))
    seen = []

    def aug(batch, rng=None):  # 1 required positional → inspected as 1-arg
        seen.append(rng)
        return batch

    list(DistributedDataLoader(data, 8, transform=aug, prefetch=0))
    assert all(r is None for r in seen)

    seen.clear()
    list(
        DistributedDataLoader(
            data, 8, transform=aug, transform_with_rng=True, prefetch=0
        )
    )
    assert all(isinstance(r, np.random.Generator) for r in seen)


def test_transform_with_rng_attribute_flag(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    data = ArrayDataset(np.ones((16, 2), dtype=np.float32))
    seen = []

    def aug(batch, rng=None):
        seen.append(rng)
        return batch

    aug.transform_with_rng = True
    list(DistributedDataLoader(data, 8, transform=aug, prefetch=0))
    assert all(isinstance(r, np.random.Generator) for r in seen)


def test_uninspectable_transform_warns(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    data = ArrayDataset(np.ones((8, 2), dtype=np.float32))
    # inspect.signature(dict) raises ValueError — the un-inspectable case.
    with pytest.warns(UserWarning, match="not inspectable"):
        DistributedDataLoader(data, 8, transform=dict)
    # Explicit declaration silences it.
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        DistributedDataLoader(data, 8, transform=dict, transform_with_rng=False)


def test_transform_with_rng_without_transform_rejected(world):
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader

    data = ArrayDataset(np.ones((8, 2), dtype=np.float32))
    with pytest.raises(ValueError, match="without transform"):
        DistributedDataLoader(data, 8, transform_with_rng=True)


# ---------------------------------------------------------------------------
# Schema checker script
# ---------------------------------------------------------------------------


def _run_checker(*args):
    return subprocess.run(
        [sys.executable, _CHECKER, *args],
        capture_output=True,
        text=True,
        timeout=60,
    )


def test_checker_rejects_json_without_a_known_schema_tag(tmp_path):
    # A .json the checker cannot tell apart is held to no schema: an
    # error, not a silent pass.
    untagged = tmp_path / "untagged.json"
    untagged.write_text(json.dumps({"metric": "m", "value": 1.0}))
    proc = _run_checker(str(untagged))
    assert proc.returncode == 1
    assert "schema" in proc.stderr


def test_checker_validates_jsonl(tmp_path):
    good = tmp_path / "good.jsonl"
    reg = MetricsRegistry(sinks=[JSONLSink(str(good))])
    reg.counter("ok").inc()
    reg.flush()
    assert _run_checker(str(good)).returncode == 0

    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        json.dumps({"schema": "wrong", "metrics": "nope"}) + "\nnot json\n"
    )
    proc = _run_checker(str(bad))
    assert proc.returncode == 1
    assert "schema" in proc.stderr and "not JSON" in proc.stderr


# ---------------------------------------------------------------------------
# Acceptance: CPU training loop → JSONL stream with everything, validated
# ---------------------------------------------------------------------------


def test_training_loop_jsonl_stream_end_to_end(world, nworkers, tmp_path):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    path = str(tmp_path / "train_metrics.jsonl")
    sink = JSONLSink(path)
    reg = get_registry()  # comm.* records here — share the stream
    reg.add_sink(sink)
    try:
        loss_fn, optimizer, state, batch = _mlp_problem()
        mon = TrainingMonitor(registry=reg, interval=2, cross_host=False)
        step = make_train_step(
            loss_fn, optimizer, donate=False, metrics=mon
        )
        st = replicate(state)
        data = shard_batch(batch)
        # An eager collective on the loop path (the cross-host loss
        # average a real loop would do) so comm.* counters are live.
        for _ in range(4):
            st, loss = step(st, data)
            fm.host_allreduce(np.asarray(float(loss)), op="mean")
    finally:
        reg.remove_sink(sink)
        sink.close()

    lines = [
        json.loads(ln)
        for ln in open(path, encoding="utf-8").read().splitlines()
    ]
    assert len(lines) == 2  # 4 steps / interval 2
    for rec in lines:
        assert validate_record(rec) == [], rec
    names = {m["name"]: m for m in lines[-1]["metrics"]}
    # Step time, examples/sec, loss, grad-norm:
    assert names["train.step_seconds"]["count"] >= 4
    assert names["train.examples_per_sec"]["value"] > 0
    assert np.isfinite(names["train.loss"]["value"])
    assert np.isfinite(names["train.grad_norm"]["value"])
    # Per-collective byte/call counters:
    # The final flush fires inside step 4's monitor tick, before that
    # iteration's host_allreduce — so the last line carries 3 of the 4.
    comm_calls = [
        m for m in lines[-1]["metrics"]
        if m["name"] == "comm.calls"
        and m["labels"].get("op") == "host_allreduce"
    ]
    assert comm_calls and comm_calls[0]["value"] >= 3
    comm_bytes = [
        m for m in lines[-1]["metrics"]
        if m["name"] == "comm.bytes"
        and m["labels"].get("op") == "host_allreduce"
    ]
    assert comm_bytes and comm_bytes[0]["value"] > 0
    # Memory stats (device.* where the backend reports them; host RSS
    # everywhere) + liveness:
    assert any(
        n.startswith(("device.memory.", "host.memory.")) for n in names
    )
    assert names["monitor.heartbeat"]["value"] == 2
    # The documented validator accepts the stream.
    assert _run_checker(path).returncode == 0


# ---------------------------------------------------------------------------
# hf_gpt2 dropout carry-over (satellite)
# ---------------------------------------------------------------------------


def test_lm_from_gpt2_carries_resid_pdrop(world):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from fluxmpi_tpu.models import lm_from_gpt2

    def tiny(**pdrops):
        cfg = transformers.GPT2Config(
            vocab_size=96, n_positions=32, n_embd=48, n_layer=2, n_head=4,
            **pdrops,
        )
        torch.manual_seed(0)
        hf = transformers.GPT2LMHeadModel(cfg)
        hf.eval()
        return hf

    # Matching nonzero pdrops: carried, no warning.
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        model, _ = lm_from_gpt2(
            tiny(resid_pdrop=0.1, embd_pdrop=0.1, attn_pdrop=0.1)
        )
    assert model.dropout == pytest.approx(0.1)

    # Divergent pdrops: resid carried, loud warning names the rest.
    with pytest.warns(UserWarning, match="attn_pdrop"):
        model, _ = lm_from_gpt2(
            tiny(resid_pdrop=0.1, embd_pdrop=0.1, attn_pdrop=0.3)
        )
    assert model.dropout == pytest.approx(0.1)

    # All-zero (the parity-test configuration): unchanged, silent.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        model, _ = lm_from_gpt2(
            tiny(resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        )
    assert model.dropout == 0.0
