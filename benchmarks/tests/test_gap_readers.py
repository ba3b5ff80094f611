"""The readers of the engine's gap ledger and of the idle time by
program span (``tick_gaps``, ``idle_by_span``), on a small hand-built
trace of plain tuples: ticks with and without an admission, an idle
engine, and an idle stretch under a runtime span nested in
``serve.decode.fetch``."""

import os

import pytest

from harness import manifest, spans

MS = 1_000_000
THREAD = "fluxmpi-serving"


def _reader(name):
    return manifest.load_module(
        os.path.join("benchmarks", "metrics", "readers", f"{name}.py")
    )


class _Cell:
    name = "no-such-cell"


def _span(name, lo_ms, hi_ms, thread=THREAD, **args):
    return (name, int(lo_ms * MS), int((hi_ms - lo_ms) * MS), thread, args)


def _deliver(at_ms, step, tokens, gap_ms, stalled, **more):
    return _span("serve.decode.deliver", at_ms, at_ms + 0.5, step=step,
                 tokens=tokens, evicted=0, gap_ms=gap_ms, stalled=stalled,
                 **more)


# The serving thread over 200 ms: an empty engine (two waits), a tick
# whose iteration admits request 7, the stalled tick after it, a clean
# tick whose fetch holds a runtime span, then 10 ms between iterations
# that no program span covers.
HOST = [
    _span("serve.idle", 0, 50, woken=0),
    _span("serve.idle", 50, 60, woken=1),
    _span("serve.iteration", 60, 100, active=2, queued=1),
    _span("serve.decode.prepare", 60, 62, active=2),
    _span("serve.decode.upload", 61, 62, bytes=1024),
    _span("serve.decode.dispatch", 62, 63, step=1, in_flight=1),
    _span("serve.decode.fetch", 63, 69, step=0),
    _deliver(69, 0, 2, 7.0, 0),
    _span("serve.admit", 70, 100, request_id=7, prompt_tokens=64,
          bucket=64, active=2),
    _span("serve.prefill", 72, 99, request_id=7, bucket=64),
    # The runtime's own name inside the prefill: ignored.
    _span("np.asarray(jax.Array)", 80, 98),
    _span("serve.iteration", 100, 130, active=3, queued=0),
    _span("serve.decode.prepare", 100, 102, active=3),
    _span("serve.decode.upload", 101, 102, bytes=1024),
    _span("serve.decode.dispatch", 102, 103, step=2, in_flight=1),
    _span("serve.decode.fetch", 103, 104, step=1),
    _deliver(104, 1, 2, 35.0, 2, stalled_by=7),
    _span("serve.iteration", 130, 190, active=3, queued=0),
    _span("serve.decode.prepare", 130, 132, active=3),
    _span("serve.decode.upload", 131, 132, bytes=1024),
    _span("serve.decode.dispatch", 132, 133, step=3, in_flight=1),
    _span("serve.decode.fetch", 133, 180, step=2),
    _span("np.asarray(jax.Array)", 134, 179),
    _deliver(180, 2, 3, 60.0, 0),
]

# The device over the same 200 ms (name, detail, start, duration): busy
# 65-75 (the tick), 75-95 (the prefill), 105-125, 135-140 and 170-178.
ROWS = [
    ("fusion.1", "", 65 * MS, 10 * MS),
    ("fusion.2", "", 75 * MS, 20 * MS),
    ("fusion.3", "", 105 * MS, 20 * MS),
    ("fusion.4", "", 135 * MS, 5 * MS),
    ("fusion.5", "", 170 * MS, 8 * MS),
]
BUSY_MS = 10 + 20 + 20 + 5 + 8
WINDOW_MS = 200


def _ctx(monkeypatch, host, rows=ROWS):
    loaded = {"window_ns": WINDOW_MS * MS, "host": host, "device": []}
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    trace = None if rows is None else {
        "rows": rows, "window_s": WINDOW_MS / 1e3, "busy_s": BUSY_MS / 1e3}
    return {"cell": _Cell(), "trace": trace, "device": {"platform": "tpu"}}


@pytest.mark.parametrize("stat,expected", [
    # 2 stalled gaps of 2 + 2 + 3.
    ("stalled_pct", 100.0 * 2 / 7),
    ("stalled_p50_ms", 35.0),
    # The clean ticks' riders: 7, 7, 60, 60, 60 ms.
    ("clean_p95_ms", 60.0),
])
def test_tick_gaps_arithmetic(monkeypatch, stat, expected):
    read = _reader("tick_gaps").read
    assert read(_ctx(monkeypatch, HOST), stat) == pytest.approx(expected)


def test_tick_gaps_counts_agree_with_the_engines_ledger():
    counted = _reader("tick_gaps").reduce(HOST)
    assert counted["gaps"] == 7 and counted["gaps_stalled"] == 2
    assert counted["ticks"] == 3 and counted["stalled_ticks"] == 1
    # sum(gap_ms x tokens): what stats()["gap_seconds"] sums.
    assert counted["gap_seconds"] == pytest.approx(
        (2 * 7.0 + 2 * 35.0 + 3 * 60.0) / 1e3)


def test_tick_gaps_edge_cases(monkeypatch):
    read = _reader("tick_gaps").read
    # The parent's trace: deliver spans without the ledger's arguments.
    parent = [_span("serve.decode.deliver", 5, 6, step=0, tokens=2,
                    evicted=0)]
    for stat in ("stalled_pct", "stalled_p50_ms", "clean_p95_ms"):
        assert read(_ctx(monkeypatch, parent), stat) is None
    # No stalled tick in the window: a share of 0 and a median of 0.
    clean = [_deliver(10, 0, 4, 5.0, 0), _deliver(20, 1, 0, 0.0, 0)]
    assert read(_ctx(monkeypatch, clean), "stalled_pct") == 0.0
    assert read(_ctx(monkeypatch, clean), "stalled_p50_ms") == 0.0
    assert read(_ctx(monkeypatch, clean), "clean_p95_ms") == 5.0
    # A tick the capture's edge cut is left out.
    cut = clean + [_deliver(WINDOW_MS - 0.2, 2, 4, 90.0, 4)]
    assert read(_ctx(monkeypatch, cut), "stalled_pct") == 0.0


# The device's idle stretches (0-65, 95-105, 125-135, 140-170, 178-200)
# against the thread's pieces, in ms by innermost program span.
IDLE_BY_SPAN_MS = {
    "serve.idle": 60.0,
    # 60-61, 100-101, 130-131: around the upload inside each.
    "serve.decode.prepare": 3.0,
    "serve.decode.upload": 3.0,
    "serve.decode.dispatch": 3.0,
    # 63-65; 103-104; 133-135, 140-170 and 178-180 (under the runtime's
    # span: the innermost PROGRAM span is the fetch).
    "serve.decode.fetch": 2.0 + 1.0 + 2.0 + 30.0 + 2.0,
    # 95-99 of the prefill, 99-100 of the admission itself.
    "serve.prefill": 4.0,
    "serve.admit": 1.0,
    "serve.decode.deliver": 0.5 + 0.5,
    # 104.5-105, 125-130 and 180.5-190: iterations between their children.
    "serve.iteration": 0.5 + 5.0 + 9.5,
    # 190-200: the thread between iterations.
    "no program span": 10.0,
}
TICK_HOST_MS = 3.0 + 3.0 + 3.0 + 37.0 + 1.0 + 15.0


def test_idle_goes_to_the_innermost_program_span():
    reader = _reader("idle_by_span")
    counted = reader.reduce(ROWS, HOST, WINDOW_MS * MS)
    assert counted["idle_ns"] == (WINDOW_MS - BUSY_MS) * MS
    by_span = {k: v / MS for k, v in counted["by_span"].items() if v}
    assert by_span == pytest.approx(IDLE_BY_SPAN_MS)
    assert sum(by_span.values()) == pytest.approx(WINDOW_MS - BUSY_MS)


def test_idle_shares_sum_to_the_idle_share(monkeypatch):
    reader = _reader("idle_by_span")
    ctx = _ctx(monkeypatch, HOST)
    empty, admit, tick = (reader.read(ctx, under) for under in reader.GROUPS)
    assert empty == pytest.approx(100.0 * 60.0 / WINDOW_MS)
    # The admission with its child; the runtime's span counts for nothing.
    assert admit == pytest.approx(100.0 * (4.0 + 1.0) / WINDOW_MS)
    assert tick == pytest.approx(100.0 * TICK_HOST_MS / WINDOW_MS)
    idle = _reader("device_idle").read(ctx)
    unattributed = 100.0 * 10.0 / WINDOW_MS
    assert empty + admit + tick + unattributed == pytest.approx(idle)


def test_idle_window_lies_where_the_devices_record_does():
    """The session outlasts the window the driver timed and the device's
    record begins late in it: the window starts at the first operation,
    or as much earlier as it must to reach back from the last one."""
    reader = _reader("idle_by_span")
    # A busy device, 100 ms: 65-165 (busy 65-95, 105-125, 135-140).
    counted = reader.reduce(ROWS, HOST, 100 * MS)
    assert counted["window"] == (65 * MS, 165 * MS)
    by_span = {k: v / MS for k, v in counted["by_span"].items() if v}
    assert by_span == pytest.approx({
        "serve.prefill": 4.0, "serve.admit": 1.0, "serve.decode.prepare": 2.0,
        "serve.decode.upload": 2.0, "serve.decode.dispatch": 2.0,
        "serve.decode.fetch": 1.0 + 2.0 + 25.0, "serve.decode.deliver": 0.5,
        "serve.iteration": 0.5 + 5.0})
    assert counted["idle_ns"] == 45 * MS
    # The harness would count the operation past the window (170-178) as
    # the window's busy time: 100 - 63 against the window's own 45.
    assert 100 * MS - counted["busy_ns"] == 37 * MS
    # 150 ms reach back from the last operation's end: 28-178.
    assert reader.reduce(ROWS, HOST, 150 * MS)["window"] == (28 * MS, 178 * MS)
    # An engine empty at both edges: the window starts with the session.
    assert reader.reduce(ROWS, HOST, WINDOW_MS * MS)["window"] == (
        0, WINDOW_MS * MS)


def test_idle_reads_nothing_without_a_device_trace_or_the_new_spans(
        monkeypatch):
    reader = _reader("idle_by_span")
    # A CPU rehearsal: no device plane.
    assert reader.read(_ctx(monkeypatch, HOST, rows=None), "serve.idle") is None
    # The parent's trace: iterations and admissions, neither marker.
    parent = [s for s in HOST
              if s[0] not in ("serve.idle", "serve.decode.upload")]
    for under in reader.GROUPS:
        assert reader.read(_ctx(monkeypatch, parent), under) is None


def test_pieces_cut_a_thread_at_every_edge():
    reader = _reader("idle_by_span")
    cut = reader.pieces([
        _span("serve.iteration", 0, 10), _span("serve.admit", 2, 6),
        _span("serve.prefill", 3, 5), _span("serve.idle", 12, 15),
    ])
    assert [(lo // MS, hi // MS, path) for lo, hi, path in cut] == [
        (0, 2, ("serve.iteration",)),
        (2, 3, ("serve.iteration", "serve.admit")),
        (3, 5, ("serve.iteration", "serve.admit", "serve.prefill")),
        (5, 6, ("serve.iteration", "serve.admit")),
        (6, 10, ("serve.iteration",)),
        (10, 12, ()),
        (12, 15, ("serve.idle",)),
    ]
    assert reader.group(("serve.iteration", "serve.admit",
                         "serve.prefill")) == "serve.admit"
    assert reader.group(("serve.iteration", "serve.decode.fetch")) == (
        "serve.iteration")
    assert reader.group(("serve.idle",)) == "serve.idle"
    assert reader.group(()) is None and reader.group(("loop.fetch",)) is None


def test_the_metrics_files_name_the_readers():
    cells = {"gpt2m-serve", "trinity-mini-serve", "sarvam-105b-serve",
             "granite-4.0-h-small-serve"}
    by_name = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in ("stalled_gap_pct", "stalled_gap_p50_ms", "clean_gap_p95_ms",
                 "decode_upload_ms", "idle_engine_empty_pct",
                 "idle_admit_pct", "idle_tick_host_pct"):
        assert set(by_name[name]["workloads"]) == cells
        assert by_name[name]["better"] == "lower"
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", f"{name}.json"))
