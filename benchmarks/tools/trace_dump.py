"""Look at a trace by hand: planes, lines, the first events of each
line with their stats; optionally write the reduction's event tuples of
a slice of the trace as JSON (how ``tests/data`` was recorded).

    python benchmarks/tools/trace_dump.py <logdir> [--json OUT --first-ms 40]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("logdir")
    parser.add_argument("--json")
    parser.add_argument("--first-ms", type=float, default=40.0)
    parser.add_argument("--events", type=int, default=12)
    args = parser.parse_args(argv)
    from jax.profiler import ProfileData

    from harness import trace

    files = sorted(glob.glob(os.path.join(
        args.logdir, "plugins", "profile", "*", "*.xplane.pb")))
    data = ProfileData.from_file(files[-1])
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[: args.events]:
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in ev.stats}
                print("    ", ev.name[:100], ev.start_ns, ev.duration_ns, stats)
    if args.json:
        events = trace.load_events(args.logdir)
        t0 = min(e[1] for rows in events["device"].values() for e in rows)
        t1 = t0 + int(args.first_ms * 1e6)
        small = {
            "device": {str(c): [e for e in rows if e[1] < t1]
                       for c, rows in events["device"].items()},
            "modules": {str(c): [e for e in rows if e[1] < t1]
                        for c, rows in events["modules"].items()},
            "host": [e for e in events["host"] if t0 <= e[1] < t1],
        }
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
