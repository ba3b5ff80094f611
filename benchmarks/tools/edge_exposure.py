"""How far does a pause of the host move a serving cell's tokens in the
window, and which ``schedule_seed`` exposes it least? No chip: a replay
of the cell's fixed schedule through a model of the engine's loop (one
tick over every live sequence, then at most one admission, whose
prefill stalls the rest).

Under its knee an open loop delivers what is offered, so
``serve_tokens_per_s`` differs between runs only by what a pause pushes
across an edge of the window: a sequence alive at the pause and still
alive at the edge is behind by the pause. The exposure is therefore the
live count near the two edges, and it fades over a sequence's lifetime,
not over a tick. A cell with long answers picks the realisation of its
Poisson schedule whose edges fall in lulls.

    python benchmarks/tools/edge_exposure.py --workload falcon-h1-34b-serve \
        --tick-ms 9.2,0.061 --prefill-ms 2.5,15.0 --seeds 1-60

``--tick-ms a,b``: a tick is ``a + b x live`` ms (two traced runs give
both); ``--prefill-ms a,b``: an admission stalls ``a + b x buckets`` ms.
Prints, a seed: the window's tokens/s and live count undisturbed, and
the root mean square, the least and the most of the change in percent
over a pause of 0.4 s placed at every whole second of the run (the change
is proportional to the pause).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import deque

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]
PAUSE_S = 0.4


def schedule(mix: dict, seconds: float) -> list[tuple[float, int, int]]:
    """``(due, prompt, answer)`` over pre-roll, window and post-roll, as
    ``drivers/serve_open_loop.make_plan`` lays them out."""
    from harness import traffic

    pre = mix["preroll_s"]
    out = []
    for base, span, stream in ((0.0, pre, 1), (pre, seconds, 0),
                               (pre + seconds, mix["postroll_s"], 2)):
        out += [(base + r["due"], len(r["prompt"]), r["max_new_tokens"])
                for r in traffic.arrivals(mix, span, 0, 2, stream=stream)]
    return sorted(out)


def replay(requests, mix: dict, seconds: float, *, slots: int, block: int,
           tick_ms, prefill_ms, pause=None) -> tuple[int, float]:
    """Tokens delivered inside the window and the mean live count there;
    ``pause = (at_s, for_s)`` stops the loop once."""
    pre = mix["preroll_s"]
    end = pre + seconds + mix["postroll_s"]
    t, i, queue, live = 0.0, 0, deque(), []
    delivered, ticks, riders = 0, 0, 0
    while t < end:
        if pause is not None and t >= pause[0]:
            t, pause = t + pause[1], None
        while i < len(requests) and requests[i][0] <= t:
            queue.append(requests[i])
            i += 1
        if not live and not queue:
            t = requests[i][0] if i < len(requests) else end
            continue
        if live:
            t += 1e-3 * (tick_ms[0] + tick_ms[1] * len(live))
            if pre <= t < pre + seconds:
                delivered += len(live)
                ticks += 1
                riders += len(live)
            live = [left - 1 for left in live if left > 1]
        if queue and len(live) < slots:
            _, prompt, answer = queue.popleft()
            t += 1e-3 * (prefill_ms[0] + prefill_ms[1] * -(-prompt // block))
            delivered += pre <= t < pre + seconds
            if answer > 1:
                live.append(answer - 1)
    return delivered, riders / max(ticks, 1)


def _pair(text: str) -> tuple[float, float]:
    a, b = text.split(",")
    return float(a), float(b)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--tick-ms", type=_pair, required=True)
    parser.add_argument("--prefill-ms", type=_pair, required=True)
    parser.add_argument("--seeds", default="1-40")
    args = parser.parse_args(argv)
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"),
              encoding="utf-8") as f:
        seconds = json.load(f)["run_seconds"]
    with open(os.path.join(BENCH_DIR, "workloads", args.workload + ".json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    mix = dict(spec["traffic"])
    model = dict(slots=spec["engine"]["slots"],
                 block=spec["engine"]["block_size"],
                 tick_ms=args.tick_ms, prefill_ms=args.prefill_ms)
    lo, _, hi = args.seeds.partition("-")
    end_s = mix["preroll_s"] + seconds + mix["postroll_s"]
    for seed in range(int(lo), int(hi or lo) + 1):
        mix["schedule_seed"] = seed
        requests = schedule(mix, seconds)
        base, live = replay(requests, mix, seconds, **model)
        moved = [
            100.0 * (replay(requests, mix, seconds, **model,
                            pause=(float(at), PAUSE_S))[0] - base) / base
            for at in range(1, int(end_s) - 1)
        ]
        rms = (sum(m * m for m in moved) / len(moved)) ** 0.5
        print(json.dumps({
            "schedule_seed": seed, "tokens_per_s": base / seconds,
            "live_mean": round(live, 1), "pause_s": PAUSE_S,
            "moved_rms_pct": round(rms, 3),
            "moved_min_pct": round(min(moved), 2),
            "moved_max_pct": round(max(moved), 2),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
