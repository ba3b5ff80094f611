"""The readers of the program's own names: host spans (``host_span``)
and device time by scope or pattern (``scope_share``), on hand-made
tuples and on a slice of a serve trace recorded on the chip (``data/``)."""

import gzip
import json
import os

import pytest

from harness import manifest, spans

MS = 1_000_000
DATA = os.path.join(os.path.dirname(__file__), "data")


def _reader(name):
    return manifest.load_module(
        os.path.join("benchmarks", "metrics", "readers", f"{name}.py")
    )


class _Cell:
    name = "no-such-cell"


def _ctx(monkeypatch, loaded, busy_s=None):
    """A reader's ``ctx`` whose cell's newest trace is ``loaded``."""
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    trace = None if busy_s is None else {"busy_s": busy_s}
    return {"cell": _Cell(), "trace": trace,
            "device": {"platform": "tpu"}}


def _tick(t0, step, active, prepare=1.0, dispatch=2.0, fetch=100.0,
          deliver=0.5):
    """One decode tick's spans from ``t0`` (ms), inside its iteration."""
    t = t0 * MS
    rows = [("serve.iteration", t, int((prepare + dispatch + fetch + deliver
                                        + 0.2) * MS), "py",
             {"active": active, "queued": 0})]
    t += int(0.1 * MS)
    for name, ms, args in (
        ("serve.decode.prepare", prepare, {"active": active}),
        ("serve.decode.dispatch", dispatch, {"step": step}),
        ("serve.decode.fetch", fetch, {"step": step}),
        ("serve.decode.deliver", deliver,
         {"step": step, "tokens": active, "evicted": 0}),
    ):
        rows.append((name, t, int(ms * MS), "py", args))
        t += int(ms * MS)
    return rows


def test_nested_spans_host_time_per_tick(monkeypatch):
    host = _tick(0, 7, 20) + _tick(110, 8, 22, prepare=2.0, deliver=1.5)
    loaded = {"window_ns": 300 * MS, "host": host, "device": []}
    read = _reader("host_span").read
    ctx = _ctx(monkeypatch, loaded)
    # prepare + dispatch + deliver of both ticks, over the two ticks:
    # the iteration around them and the blocking fetch are not in it.
    assert read(ctx, ["serve.decode.prepare", "serve.decode.dispatch",
                      "serve.decode.deliver"],
                per="serve.decode.dispatch") == pytest.approx(
        (1.0 + 2.0 + 0.5 + 2.0 + 2.0 + 1.5) / 2)
    assert read(ctx, ["serve.decode.prepare"], arg="active") == 21.0
    assert read(ctx, ["serve.decode.fetch"], stat="median") == 100.0
    assert read(ctx, ["serve.iteration"]) == pytest.approx(
        (103.7 + 105.7) / 2)


@pytest.mark.parametrize("cut", ["start", "end"])
def test_a_span_the_captures_edge_cut_is_left_out(monkeypatch, cut):
    """A ring span that began before the session, or was open at its
    end, has no true duration; the whole ones still count."""
    whole = ("serve.admit", 50 * MS, 60 * MS, "py", {"request_id": 1})
    edge = (("serve.admit", -20 * MS, 40 * MS, "py", {"request_id": 0})
            if cut == "start" else
            ("serve.admit", 180 * MS, 40 * MS, "py", {"request_id": 2}))
    loaded = {"window_ns": 200 * MS, "host": [edge, whole], "device": []}
    ctx = _ctx(monkeypatch, loaded)
    assert _reader("host_span").read(
        ctx, ["serve.admit"], stat="median") == 60.0
    assert spans.whole([edge], 200 * MS) == []


def test_no_matching_span_reads_as_nothing(monkeypatch):
    loaded = {"window_ns": 200 * MS, "host": _tick(0, 1, 3), "device": []}
    ctx = _ctx(monkeypatch, loaded)
    read = _reader("host_span").read
    assert read(ctx, ["loop.flush"]) is None
    assert read(ctx, ["serve.decode.prepare"], arg="no_such") is None
    # The parent of the PR that added the spans: an empty host plane.
    empty = {"window_ns": 200 * MS, "host": [], "device": []}
    assert read(_ctx(monkeypatch, empty), ["serve.admit"]) is None


def test_scope_share_by_scope_and_by_pattern(monkeypatch):
    scoped = [
        ("while.3", "jit(step)/kv_gather/while", 0, 90 * MS, "%while.3 ="),
        ("copy.1", "jit(step)/jit(main)/kv_gather/gather", 0, 30 * MS, ""),
        ("fusion.2", "jit(step)/kv_write/scatter", 30 * MS, 10 * MS, ""),
        ("fusion.9", "jit(f)/transpose(jvp(ce_head))/dot", 40 * MS, 20 * MS,
         ""),
        ("fusion.4", "jit(step)/decode_attention/mul", 60 * MS, 40 * MS, ""),
        ("copy.5", "jit(step)/not_kv_gather/x", 100 * MS, 5 * MS, ""),
    ]
    read = _reader("scope_share").read
    ctx = _ctx(monkeypatch, {"window_ns": 0, "host": [], "device": scoped},
               busy_s=0.2)
    # The container is not work; a scope is a whole path component.
    assert read(ctx, ["kv_gather", "kv_write"]) == pytest.approx(20.0)
    assert read(ctx, ["ce_head"], pattern="^copy") == pytest.approx(10.0)
    assert read(ctx, ["batch_gather"]) is None
    # The chip's trace today: no scope path on any operation, so the
    # pattern reads names and instruction text.
    bare = [(n, "", s, d, f"%{n} = bf16[32,16,1024,64] copy(x)")
            for n, _, s, d, _ in scoped]
    ctx = _ctx(monkeypatch, {"window_ns": 0, "host": [], "device": bare},
               busy_s=0.2)
    assert read(ctx, ["kv_gather"], pattern=r"^copy[.\d]*$") == (
        pytest.approx(17.5))
    assert read(ctx, ["kv_gather"], pattern=r"\[32,16,1024,64\] copy") == (
        pytest.approx(100.0 * 0.105 / 0.2))
    assert read(ctx, ["kv_gather"]) is None
    assert read(_ctx(monkeypatch, {"device": bare}, busy_s=None),
                ["kv_gather"], pattern="^copy") is None


def test_ring_export_stands_in_for_an_empty_host_plane(tmp_path):
    """A capture without host events: the ring's export beside it,
    wall-clock microseconds, rebased on the session's start."""
    start_unix_ns = 1_790_000_000 * 10**9
    events = [
        {"name": "loop.fetch", "ph": "X", "ts": start_unix_ns / 1e3 + 1500.0,
         "dur": 250.0, "pid": 1, "tid": 5, "args": {"update": 3}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 5,
         "args": {"name": "main"}},
        {"name": "PjitFunction(f)", "ph": "X",
         "ts": start_unix_ns / 1e3 + 10.0, "dur": 5.0, "pid": 1, "tid": 5},
        {"name": "loop.fetch", "ph": "X", "ts": start_unix_ns / 1e3 - 9e6,
         "dur": 250.0, "pid": 1, "tid": 5, "args": {"update": 0}},
    ]
    path = tmp_path / spans.RING_FILE
    path.write_text(json.dumps({"traceEvents": events}))
    got = spans.ring_spans(str(path), start_unix_ns, 2 * 10**9)
    assert [(s[0], s[2], s[4]) for s in got] == [
        ("loop.fetch", 250_000, {"update": 3})]
    assert abs(got[0][1] - 1_500_000) <= 1000  # float microseconds
    assert spans.ring_spans(str(tmp_path / "absent.json"), 0, 1) == []


def test_every_new_metric_names_a_reader_and_its_arguments():
    import inspect

    for name in ("decode_host_ms", "prefill_stall_ms", "decode_active_slots",
                 "prefill_device_ms", "kv_gather_device_pct",
                 "loop_flush_block_ms", "train_program_device_ms",
                 "ce_head_device_pct", "flash_fwd_roofline",
                 "flash_dq_roofline", "flash_dkv_roofline"):
        with open(os.path.join(manifest.BENCH_DIR, "metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        params = inspect.signature(_reader(spec["reader"]).read).parameters
        assert set(spec.get("args", {})) <= set(params) - {"ctx"}, name


def test_recorded_serve_slice():
    """1.2 s of a traced ``gpt2m-serve`` window (host spans) with the
    device's operations of its first 125 ms, as ``tools/span_dump.py``
    wrote them."""
    with gzip.open(os.path.join(
            DATA, "trace_gpt2m_serve.spans.json.gz"), "rt") as f:
        raw = json.load(f)
    with open(os.path.join(DATA, "trace_gpt2m_serve.spans.expected.json")) as f:
        want = json.load(f)
    host = [tuple(s) for s in raw["host"]]
    device = [tuple(r) for r in raw["device"]]
    whole = spans.whole(host, raw["window_ns"])
    assert len(host) - len(whole) == want["cut_by_the_edge"]
    counts = {}
    for s in whole:
        counts[s[0]] = counts.get(s[0], 0) + 1
    assert counts == want["counts"]
    ticks = spans.named(whole, "serve.decode.dispatch")
    host_ms = sum(s[2] for s in spans.named(whole, [
        "serve.decode.prepare", "serve.decode.dispatch",
        "serve.decode.deliver"])) / 1e6 / len(ticks)
    assert host_ms == pytest.approx(want["decode_host_ms"], rel=1e-9)
    # Nesting by containment: every tick's spans lie in an iteration.
    iterations = spans.named(host, "serve.iteration")
    for s in spans.named(whole, ["serve.decode.prepare", "serve.admit"]):
        assert any(i[1] <= s[1] and s[1] + s[2] <= i[1] + i[2]
                   for i in iterations), s
    seconds, count, how = spans.scope_seconds(
        device, ["kv_gather", "kv_write"], want["pattern"])
    assert how == "pattern" and count == want["pattern_count"]
    assert seconds == pytest.approx(want["pattern_seconds"], rel=1e-9)
