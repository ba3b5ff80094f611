"""The program's own names in a profiler trace: its host spans
(``tracing.span`` -> ``TraceAnnotation`` events on the host plane, with
their arguments) and the ``jax.named_scope`` paths of the device's
operations.

``harness/trace.py`` reduces a trace to what the first metrics needed
and keeps neither an event's arguments nor an operation's scope; the
readers that need them (``metrics/readers/host_span.py``,
``scope_share.py``) load the cell's newest trace themselves, through
here, once per process. Like the reduction there, everything past
``load`` works on plain tuples, so that it can be checked on a small
recorded trace (``benchmarks/tests/data``).

A program without spans or scopes (the parent of the PR that added them)
gives empty lists, and the readers then return None.
"""

from __future__ import annotations

import glob
import json
import os
import re

from harness import trace as trace_mod

# First components of the names the program gives its spans.
PROGRAM = re.compile(r"^(loop|serve|train|data|request|comm)\.")
# The ring's export of a capture made through utils.profile_trace, for
# captures that drop host events (host_tracer_level 0).
RING_FILE = "fluxmpi_spans.trace.json"
# The stat under which a profiler that resolves operation metadata gives
# an operation its scope path ("jit(step)/jit(main)/kv_gather/gather").
# A v5e trace taken with ``enable_hlo_proto`` off has no such stat, and
# with it on the paths sit in the ``/host:metadata`` plane's HLO protos,
# not on the events (PERF.md, section 7): until a reader of those fills
# ``scope``, it is empty and ``scope_seconds`` falls back to its pattern.
SCOPE_STAT = "tf_op"
# How much of an operation's instruction text is kept for patterns
# (result shapes, opcode, the first operands).
TEXT_CHARS = 400

_cache: dict[tuple, dict] = {}


def newest(logdir: str) -> str | None:
    files = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def load(logdir: str, device_ops: bool = True) -> dict:
    """``{"window_ns": session length, "start_unix_ns": its start on the
    wall clock, "host": [(name, start_ns, dur_ns, thread, args)],
    "device": [(name, scope, start_ns, dur_ns, text)]}`` of the newest
    trace under ``logdir``: the program's spans of the host plane (from
    the ring's export beside it where the host plane has none) and the
    first chip's operations with their scope path (empty where the trace
    carries none) and the head of their instruction text. Times count
    from the session's start. Cached by file. ``device_ops=False`` skips
    the operations (a long capture holds millions)."""
    path = newest(logdir)
    if path is None:
        return {"window_ns": 0, "start_unix_ns": 0, "host": [], "device": []}
    if (path, device_ops) in _cache:
        return _cache[path, device_ops]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: list = []
    device: dict[int, list] = {}
    start_unix = stop_unix = None
    for plane in data.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines if device_ops else ():
                if line.name != trace_mod.OPS_LINE:
                    continue
                rows = device.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    rows.append((
                        trace_mod.split_name(ev.name)[0],
                        str(dict(ev.stats).get(SCOPE_STAT, "")),
                        int(ev.start_ns), int(ev.duration_ns),
                        ev.name[:TEXT_CHARS],
                    ))
        elif plane.name == trace_mod.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if PROGRAM.match(ev.name):
                        host.append((
                            ev.name, int(ev.start_ns), int(ev.duration_ns),
                            line.name, dict(ev.stats),
                        ))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_unix = stats.get("profile_start_time")
            stop_unix = stats.get("profile_stop_time")
    window_ns = (
        int(stop_unix) - int(start_unix)
        if start_unix is not None and stop_unix is not None else 0
    )
    if not host and start_unix is not None:
        host = ring_spans(
            os.path.join(logdir, RING_FILE), int(start_unix), window_ns
        )
    out = {
        "window_ns": window_ns,
        "start_unix_ns": int(start_unix or 0),
        "host": sorted(host, key=lambda e: e[1]),
        "device": device[min(device)] if device else [],
    }
    _cache[path, device_ops] = out
    return out


def ring_spans(path: str, start_unix_ns: int, window_ns: int) -> list:
    """The program's spans of a ring export (wall-clock microseconds),
    rebased on the session's start; only those that touch the session."""
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        events = json.load(f).get("traceEvents", [])
    out = []
    for ev in events:
        if ev.get("ph") != "X" or not PROGRAM.match(ev.get("name", "")):
            continue
        start = int(ev["ts"] * 1e3) - start_unix_ns
        dur = int(ev["dur"] * 1e3)
        if start + dur < 0 or start > window_ns:
            continue
        out.append((ev["name"], start, dur, str(ev.get("tid", "")),
                    dict(ev.get("args") or {})))
    return out


def for_cell(ctx: dict) -> dict:
    """The loaded trace of the run ``ctx`` describes: per-layer readers
    run only under ``--trace 1``, after the driver wrote the cell's
    trace. (A CPU rehearsal has no device plane and its reduction is
    None; its host plane still holds the program's spans.)"""
    from harness import manifest

    return load(os.path.join(
        manifest.ROOT, ".bench_out", "trace", ctx["cell"].name
    ))


# ---------------------------------------------------------------------------
# Reductions, on plain tuples
# ---------------------------------------------------------------------------


def whole(spans: list, window_ns: int) -> list:
    """Leave out the spans the capture's edges cut: one that began
    before the session or was open at its end has no true duration. (A
    ``TraceAnnotation`` open at either edge is not recorded at all; a
    ring span is, and shows here with a start below zero or an end past
    the window.)"""
    return [s for s in spans
            if s[1] >= 0 and (not window_ns or s[1] + s[2] <= window_ns)]


def named(spans: list, names) -> list:
    names = set([names] if isinstance(names, str) else names)
    return [s for s in spans if s[0] in names]


def scope_seconds(device: list, scopes, pattern: str | None = None):
    """Device seconds of the operations under one of ``scopes`` (a
    component of the scope path, also inside ``jvp(...)`` /
    ``transpose(...)``), and how many; where no operation carries a
    scope path at all and ``pattern`` is given, of the operations whose
    name or instruction text matches it. Containers (``while``) are left
    out, as in the reduction's busy time. Returns ``(seconds, count,
    how)``."""
    rows = [r for r in device if not trace_mod.CONTAINER.match(r[0])]
    if any(r[1] for r in rows):
        rx = re.compile(
            r"(^|[/(])(" + "|".join(map(re.escape, scopes)) + r")([/)]|$)"
        )
        hits = [r[3] for r in rows if rx.search(r[1])]
        how = "scope"
    elif pattern is not None:
        rx = re.compile(pattern)
        hits = [r[3] for r in rows if rx.search(r[0]) or rx.search(r[4])]
        how = "pattern"
    else:
        return 0.0, 0, "none"
    return sum(hits) / 1e9, len(hits), how
