"""Look at the program's own names in a trace by hand: its host spans
(count, mean and total by name), the device time by scope where the
trace carries scope paths, and optionally

- ``--json OUT --from-ms A --ms N --device-ms M``: write a slice of the
  loaded trace as the plain tuples the readers' reductions work on, the
  device's operations for its first M milliseconds only (how
  ``tests/data/trace_gpt2m_serve.spans.json.gz`` was recorded);
- ``--pattern RX``: the device time by kind of the operations whose name
  or instruction text matches (what a ``scope_share`` pattern catches);
- ``--ring FILE``: compare the span ring's export of the same run
  (``FLUXMPI_TPU_TRACE=<file>``) with the trace's host plane: each span
  found in both, matched by name and arguments, gives one reading of the
  ring's clock (wall clock, rebased from ``perf_counter``) against the
  profiler's; prints the offset at the first and the last match and the
  drift between them.

    python benchmarks/tools/span_dump.py <logdir> [--ring FILE] [--json OUT]
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def clock_offsets(loaded: dict, ring_path: str) -> list[tuple[float, float]]:
    """``(seconds into the session, ring start - xplane start in us)``
    for every span present in both channels; a name with arguments that
    occurs twice in either (``serve.decode.prepare`` with the same
    ``active``) identifies no span and is left out."""
    from collections import Counter

    from harness import spans as spans_mod

    def key(span):
        args = {k: (int(v) if str(v).lstrip("-").isdigit() else v)
                for k, v in span[4].items()}
        return span[0], json.dumps(args, sort_keys=True)

    ring = spans_mod.ring_spans(
        ring_path, loaded["start_unix_ns"], loaded["window_ns"]
    )
    seen = Counter(key(s) for s in ring) + Counter(
        key(s) for s in loaded["host"]
    )
    twins = {key(s): s for s in ring}
    return sorted(
        (s[1] / 1e9, (twins[key(s)][1] - s[1]) / 1e3)
        for s in loaded["host"] if seen[key(s)] == 2
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("logdir")
    parser.add_argument("--ring")
    parser.add_argument("--json")
    parser.add_argument("--from-ms", type=float, default=0.0)
    parser.add_argument("--ms", type=float, default=400.0)
    parser.add_argument("--device-ms", type=float, default=130.0)
    parser.add_argument("--pattern")
    parser.add_argument("--host-only", action="store_true",
                        help="skip the device's operations (long captures)")
    args = parser.parse_args(argv)

    import re

    from harness import spans as spans_mod
    from harness import trace as trace_mod

    loaded = spans_mod.load(args.logdir, device_ops=not args.host_only)
    print("session", loaded["window_ns"] / 1e9, "s;",
          len(loaded["host"]), "program spans;",
          len(loaded["device"]), "device operations")
    by_name = defaultdict(list)
    for s in spans_mod.whole(loaded["host"], loaded["window_ns"]):
        by_name[s[0]].append(s[2] / 1e6)
    for name, ms in sorted(by_name.items()):
        print(f"  {name:28s} n={len(ms):5d} mean={statistics.fmean(ms):9.3f} ms"
              f" median={statistics.median(ms):9.3f} total={sum(ms):10.3f}")
    by_scope = defaultdict(float)
    for _, scope, _, dur, _ in loaded["device"]:
        if scope:
            by_scope[scope.rsplit("/", 1)[0]] += dur / 1e9
    for scope, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:20]:
        print(f"  scope {scope[:90]:90s} {s:8.4f} s")
    if not by_scope:
        print("  no operation of the trace carries a scope path")
    if args.pattern:
        rx = re.compile(args.pattern)
        caught = defaultdict(float)
        for name, _, _, dur, text in loaded["device"]:
            if rx.search(name) or rx.search(text):
                caught[trace_mod.category(name)] += dur / 1e9
        for kind, s in sorted(caught.items(), key=lambda kv: -kv[1])[:25]:
            print(f"  pattern {kind:60s} {s:8.4f} s")
    if args.ring:
        pairs = clock_offsets(loaded, args.ring)
        if not pairs:
            print("no span found in both channels")
            return 1
        offs = [o for _, o in pairs]
        print(json.dumps({
            "matched": len(pairs),
            "first": {"at_s": pairs[0][0], "ring_minus_xplane_us": pairs[0][1]},
            "last": {"at_s": pairs[-1][0], "ring_minus_xplane_us": pairs[-1][1]},
            "drift_us": pairs[-1][1] - pairs[0][1],
            "median_us": statistics.median(offs),
            "min_us": min(offs), "max_us": max(offs),
        }))
    if args.json:
        lo = int(args.from_ms * 1e6)
        hi = lo + int(args.ms * 1e6)
        small = {
            "window_ns": hi - lo,
            "host": [[s[0], s[1] - lo, s[2], s[3], s[4]]
                     for s in loaded["host"] if s[1] + s[2] > lo and s[1] < hi],
            "device": [[r[0], r[1], r[2] - lo, r[3], r[4]]
                       for r in loaded["device"]
                       if lo <= r[2] < lo + int(args.device_ms * 1e6)],
        }
        opener = gzip.open if args.json.endswith(".gz") else open
        with opener(args.json, "wt", encoding="utf-8") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
