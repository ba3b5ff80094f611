"""Why the chip sat idle: the first chip's idle time in the traced
window, put down to what the serving thread was doing in it.

The idle stretches are recomputed from the reduction's ``rows`` as
``harness/trace.reduce`` computes ``busy_s`` (the complement of the
union of the operations' intervals), inside a window of the reduction's
``window_s``. Where that window lies in the session has to be found: on
the chip a session is a third of a second longer than the window the
driver times (its stop is stamped after the tracers have stopped), the
device's record begins ~50 ms into it (a busy cell's first operation),
the host's spans end with the driver's window and the device's
operations 40-90 ms after that (queued work, while the tracers stop).
The window starts at the first operation, or as much earlier as it
must to reach back ``window_s`` from the last one (an engine that is
empty at either edge: there it starts with the session). The thread's
time is cut at
every edge of the program's own spans (``harness.spans.PROGRAM``; the
runtime's names are ignored) and each piece belongs to the INNERMOST
span open over it; an idle stretch is shared out over the pieces it
overlaps. ``under`` chooses the share of the window:

- ``serve.idle``: the engine was empty, asleep on its wake event;
- ``serve.admit``: inside an admission, its children included (the
  prefill's dispatch and the blocking read of the first token);
- ``serve.iteration``: inside a scheduler pass and outside an admission
  (the decode tick's host turn).

Idle time under no program span is "unattributed"; the three shares and
it are the window's idle share, which is ``device_idle_pct`` where no
operation lies outside the window (the harness counts those as busy
time of the window too, and reads up to a point low for them in a busy
cell: the printed line gives both). Prints
the seconds by innermost span on a line of its own; returns None without
a device trace, and on a trace with neither ``serve.idle`` nor
``serve.decode.upload`` (a program from before them)."""

import json
from collections import defaultdict

from harness import spans as spans_mod
from harness import trace as trace_mod

GROUPS = ("serve.idle", "serve.admit", "serve.iteration")
MARKERS = ("serve.idle", "serve.decode.upload")


def pieces(host: list) -> list:
    """``[(lo, hi, path)]``: one thread's time cut at every span's edge,
    each piece under the names of the spans open over it, outermost
    first (``()`` between spans). Spans nest by containment; a child
    that outlasts its parent by the clock's grain ends with it."""
    out, stack, cursor = [], [], None

    def close(upto):
        nonlocal cursor
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > cursor:
                out.append((cursor, end, tuple(n for n, _ in stack) + (name,)))
                cursor = end

    for name, start, dur, *_ in sorted(host, key=lambda s: (s[1], -s[2])):
        if cursor is None:
            cursor = start
        close(start)
        if start > cursor:
            out.append((cursor, start, tuple(n for n, _ in stack)))
            cursor = start
        end = start + dur
        stack.append((name, min(end, stack[-1][1]) if stack else end))
    close(float("inf"))
    return out


def shared_out(idle: list, cut: list) -> dict:
    """Nanoseconds of the disjoint sorted ``idle`` intervals under each
    path of ``cut`` (``pieces``), and under ``None`` where no piece
    lies."""
    out: dict = defaultdict(int)
    at = 0
    for lo, hi in idle:
        left = hi - lo
        while at < len(cut) and cut[at][1] <= lo:
            at += 1
        k = at
        while k < len(cut) and cut[k][0] < hi:
            over = min(hi, cut[k][1]) - max(lo, cut[k][0])
            if over > 0:
                out[cut[k][2]] += over
                left -= over
            k += 1
        out[None] += left
    return out


def group(path) -> str | None:
    """Which of ``GROUPS`` a piece under ``path`` counts for."""
    if not path:
        return None
    for name in GROUPS[:2]:
        if name in path:
            return name
    return GROUPS[2] if path[0] == GROUPS[2] else None


def reduce(rows: list, host: list, window_ns: int) -> dict | None:
    """Idle nanoseconds of the window by group and by innermost span."""
    host = [s for s in host if spans_mod.PROGRAM.match(s[0])]
    if not any(s[0] in MARKERS for s in host):
        return None
    busy = trace_mod._union([(s, s + d) for _, _, s, d in rows])
    lo = min(busy[0][0], max(0, busy[-1][1] - window_ns))
    hi = lo + window_ns
    edges = [lo] + [t for a, b in busy for t in (a, b)] + [hi]
    idle = [(max(a, lo), min(b, hi)) for a, b in zip(edges[::2], edges[1::2])
            if min(b, hi) > max(a, lo)]
    # The serving thread: the one whose spans are the engine's.
    threads = {s[3] for s in host if s[0].startswith("serve.")}
    by_path = shared_out(idle, pieces([s for s in host if s[3] in threads]))
    total = sum(b - a for a, b in idle)
    by_group: dict = defaultdict(int)
    by_span: dict = defaultdict(int)
    for path, ns in by_path.items():
        by_group[group(path)] += ns
        by_span[path[-1] if path else "no program span"] += ns
    return {"idle_ns": total, "by_group": dict(by_group),
            "by_span": dict(by_span), "window": (lo, hi),
            "operations": (busy[0][0], busy[-1][1]),
            "busy_ns": sum(b - a for a, b in busy)}


def read(ctx, under):
    trace = ctx.get("trace")
    if trace is None:
        return None
    loaded = spans_mod.for_cell(ctx)
    window_ns = int(trace["window_s"] * 1e9)
    counted = reduce(trace["rows"], loaded["host"], window_ns)
    if counted is None:
        return None
    print(json.dumps({"idle_by_span": {
        "idle_s": counted["idle_ns"] / 1e9,
        "window_s": [t / 1e9 for t in counted["window"]],
        "session_s": loaded["window_ns"] / 1e9,
        "operations_s": [t / 1e9 for t in counted["operations"]],
        # What device_idle_pct divides by the same window: every
        # operation's time, those outside the window too.
        "idle_by_all_operations_s": (window_ns - counted["busy_ns"]) / 1e9,
        "unattributed_s": counted["by_group"].get(None, 0) / 1e9,
        "by_group_s": {g: counted["by_group"].get(g, 0) / 1e9
                       for g in GROUPS},
        "by_innermost_span_s": {k: v / 1e9 for k, v in sorted(
            counted["by_span"].items(), key=lambda kv: -kv[1])},
    }}), flush=True)
    return 100.0 * counted["by_group"].get(under, 0) / window_ns
