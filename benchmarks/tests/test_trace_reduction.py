"""The reduction from trace events to numbers, on hand-made events and
on a small trace recorded on the chip (``data/``)."""

import gzip
import json
import os

import pytest

from harness import trace

MS = 1_000_000


def test_busy_is_the_union_and_containers_are_not_work():
    events = {"device": {0: [
        ("while.1", "", 0, 100 * MS),          # a loop around the rest
        ("fusion.1", "", 0, 10 * MS),
        ("fusion.2", "", 5 * MS, 10 * MS),     # overlaps fusion.1
        ("flash_attention.3", "%flash_attention.3 = bf16[8,16]{1,0} custom-call(",
         40 * MS, 10 * MS),
    ]}, "host": [("dispatch", 16 * MS, 20 * MS, "main"),
                 ("everything", 0, 100 * MS, "main")]}
    r = trace.reduce(events)
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["window_s"] == pytest.approx(0.050)
    assert r["idle_gaps"] == {"dispatch": pytest.approx(0.025)}
    assert trace.matching_seconds(r, r"^%flash_attention[.\d]* = bf16\[") == (
        pytest.approx(0.010), 1)
    assert trace.breakdown(r)["device_ops"] == [
        ["fusion", pytest.approx(0.020)],
        ["flash_attention", pytest.approx(0.010)],
    ]


def test_the_chip_names_an_operation_by_its_whole_instruction():
    text = "%fusion.6914 = f32[8192,1024]{1,0:T(8,128)S(1)} fusion(bf16[8192,8192] %x)"
    name, detail = trace.split_name(text)
    assert name == "fusion.6914" and detail.startswith("%fusion.6914 = f32[")
    assert trace.category(name) == "fusion"
    assert trace.category("all-gather-start.12.1") == "all-gather-start"


def test_collective_time_not_hidden_behind_compute():
    events = {"device": {
        0: [("fusion.1", "", 0, 10 * MS), ("all-gather.2", "", 5 * MS, 10 * MS)],
        1: [("fusion.1", "", 0, 10 * MS), ("all-gather.2", "", 5 * MS, 10 * MS)],
    }, "host": []}
    r = trace.reduce(events, window_s=0.02)
    assert r["chips"] == 2
    assert r["collective_exposed_s"] == pytest.approx(0.005)
    assert r["busy_s"] == pytest.approx(0.015)
    assert r["window_s"] == 0.02


def test_nothing_on_the_device_reads_as_nothing():
    assert trace.reduce({"device": {}, "host": []}) is None


def test_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_gpt2m_train.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    events = {
        "device": {int(c): [tuple(e) for e in rows]
                   for c, rows in raw["device"].items()},
        "modules": {int(c): [tuple(e) for e in rows]
                    for c, rows in raw["modules"].items()},
        "host": [tuple(e) for e in raw["host"]],
    }
    r = trace.reduce(events)
    assert r is not None and 0.0 < r["busy_s"] <= r["window_s"]
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_gpt2m_train.expected.json")) as f:
        want = json.load(f)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for kind, pattern in want["kernels"].items():
        seconds, calls = trace.matching_seconds(r, pattern)
        assert calls == want["calls"][kind]
        assert seconds == pytest.approx(want["seconds"][kind], rel=1e-9)
