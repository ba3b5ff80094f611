"""From a profiler trace to numbers: device busy time, time by
operation, idle gaps by what the host was doing.

The reduction works on plain event tuples, so that it can be checked on
a small recorded trace (``benchmarks/tests/data``): ``load_events``
turns the profiler's ``.xplane.pb`` into them with nothing but JAX.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DETAIL_CHARS = 160
# Events on the ops line that only contain other operations (a loop's
# body runs inside its ``while`` event): they are not work themselves.
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]|$)")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
HOST_PLANE = "/host:CPU"


def split_name(text: str) -> tuple[str, str]:
    """The chip's trace names an operation by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``): the short name before the
    ``=``, and the head of the text (result shapes, opcode) as detail,
    which is all that tells one unnamed kernel call from another."""
    return text.split(" = ", 1)[0].lstrip("%"), text[:DETAIL_CHARS]


def category(name: str) -> str:
    """``fusion.12`` -> ``fusion``: one row per kind of operation."""
    return re.sub(r"[.\d]+$", "", name)


def start(logdir: str, host_tracer_level: int = 1) -> None:
    """Start the profiler without the Python tracer (which slows a
    host-driven loop and swells the file). ``host_tracer_level`` 0 also
    drops the host's TraceMe spans: where the host feeds the device batch
    by batch they slow the feed several times over, and the traced window
    then reads an idle share that no untraced run has."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_tracer_level
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)


def load_events(logdir: str) -> dict:
    """``{"device": {chip: [(name, detail, start_ns, dur_ns)]},
    "modules": {chip: [(name, start_ns, dur_ns)]},
    "host": [(name, start_ns, dur_ns, thread)]}`` from the newest trace
    under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        return {"device": {}, "modules": {}, "host": []}
    data = ProfileData.from_file(files[-1])
    device: dict[int, list] = {}
    modules: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events
                    )
                if line.name != OPS_LINE:
                    continue
                rows = device.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    rows.append((*split_name(ev.name), int(ev.start_ns),
                                 int(ev.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), line.name))
    return {"device": device, "modules": modules, "host": host}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _covered(intervals, merged) -> int:
    """Nanoseconds of ``intervals`` (disjoint or not) lying inside the
    disjoint sorted ``merged``."""
    total = 0
    for lo, hi in _union(intervals):
        for a, b in merged:
            if b <= lo:
                continue
            if a >= hi:
                break
            total += min(hi, b) - max(lo, a)
    return total


def reduce(events: dict, window_s: float | None = None) -> dict | None:
    """The numbers the per-layer metrics read. Returns None when no
    operation ran on a device. ``busy_s`` is the union of the intervals
    in which an operation ran, averaged over the chips; ``window_s``
    defaults to the span from the first operation's start to the last
    one's end over all chips."""
    chips = {c: [e for e in rows if not CONTAINER.match(e[0])]
             for c, rows in events["device"].items()}
    chips = {c: rows for c, rows in chips.items() if rows}
    if not chips:
        return None
    t0 = min(e[2] for rows in chips.values() for e in rows)
    t1 = max(e[2] + e[3] for rows in chips.values() for e in rows)
    span_s = (t1 - t0) / 1e9
    busy, exposed, by_name = [], [], defaultdict(float)
    gaps: list[tuple[int, int]] = []
    for chip, rows in chips.items():
        merged = _union([(s, s + d) for _, _, s, d in rows])
        busy.append(sum(hi - lo for lo, hi in merged) / 1e9)
        compute = _union([(s, s + d) for n, _, s, d in rows
                          if not COLLECTIVE.match(n)])
        coll = [(s, s + d) for n, _, s, d in rows if COLLECTIVE.match(n)]
        coll_total = sum(hi - lo for lo, hi in _union(coll))
        exposed.append((coll_total - _covered(coll, compute)) / 1e9)
        for n, _, _, d in rows:
            by_name[category(n)] += d / 1e9 / len(chips)
        if chip == min(chips):
            starts = sorted((s, category(n)) for n, _, s, _ in rows)
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return {
        "window_s": float(window_s) if window_s else span_s,
        "span_s": span_s,
        "busy_s": sum(busy) / len(busy),
        "collective_exposed_s": sum(exposed) / len(exposed),
        "chips": len(chips),
        "by_name": dict(by_name),
        "idle_gaps": _attribute(gaps, events["host"], starts),
        "rows": chips[min(chips)],
        "modules": events.get("modules", {}).get(min(chips), []),
    }


def _attribute(gaps, host, starts, top: int = 200) -> dict:
    """Seconds of the longest device idle gaps (first chip), by the
    shortest host span that covers each gap's middle; where the host
    recorded none, by the kind of operation the device ran next."""
    import bisect

    out: dict[str, float] = defaultdict(float)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = sorted(host, key=lambda e: e[2])
    for lo, hi in gaps:
        mid = (lo + hi) // 2
        name = next((n for n, s, d, _ in host if s <= mid < s + d and d > 0),
                    None)
        if name is None:
            i = bisect.bisect_left(starts, (hi, ""))
            name = ("no host span; device waited before "
                    + (starts[i][1] if i < len(starts) else "the end"))
        out[name] += (hi - lo) / 1e9
    return dict(out)


def matching_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Device seconds and count of the first chip's operations whose
    name or detail matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [d for n, detail, _, d in reduced["rows"]
            if rx.search(n) or rx.search(detail)]
    return sum(hits) / 1e9, len(hits)


def module_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Device seconds and count of the first chip's program executions
    (the modules line) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [d for n, _, d in reduced["modules"] if rx.search(n)]
    return sum(hits) / 1e9, len(hits)


def breakdown(reduced: dict) -> dict:
    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(reduced["by_name"]),
            "idle_gaps": top(reduced["idle_gaps"])}
