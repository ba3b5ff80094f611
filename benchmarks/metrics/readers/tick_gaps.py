"""The gaps between tokens as the engine accounts them, from the
``serve.decode.deliver`` spans of the cell's newest trace: every tick
says how many riders it delivered to (``tokens``), their mean gap since
their last token (``gap_ms``) and how many of those gaps held another
request's admission (``stalled``).

``stat`` chooses the number: ``stalled_pct``, the stalled gaps over all
gaps of the window; ``stalled_p50_ms``, the median ``gap_ms`` of the
ticks with a stalled rider (0 where the window holds none); or
``clean_p95_ms``, the 95th percentile of ``gap_ms`` over the ticks with
none, each tick counted once a rider: what ``itl_p95_ms`` would read
were no gap stalled. Spans the capture's edges cut are left out. Prints
what it counted on a line of its own; returns None where no tick carries
``gap_ms`` (a program from before the gap ledger)."""

import json
import statistics

import numpy as np

from harness import spans as spans_mod


def reduce(host: list) -> dict | None:
    """The three numbers, and what they were counted from, of the
    ``serve.decode.deliver`` spans among ``host``."""
    ticks = [
        (float(a["gap_ms"]), int(float(a["tokens"])), int(float(a["stalled"])))
        for a in (s[4] for s in spans_mod.named(host, "serve.decode.deliver"))
        if "gap_ms" in a and float(a["tokens"]) > 0
    ]
    if not ticks:
        return None
    gaps = sum(n for _, n, _ in ticks)
    gaps_stalled = sum(hit for _, _, hit in ticks)
    stalled = [gap for gap, _, hit in ticks if hit]
    clean = [(gap, n) for gap, n, hit in ticks if not hit]
    return {
        "ticks": len(ticks), "gaps": gaps,
        "stalled_ticks": len(stalled), "gaps_stalled": gaps_stalled,
        "gap_seconds": sum(gap * n for gap, n, _ in ticks) / 1e3,
        "stalled_pct": 100.0 * gaps_stalled / gaps,
        "stalled_p50_ms": statistics.median(stalled) if stalled else 0.0,
        "clean_p95_ms": float(np.percentile(
            np.repeat([gap for gap, _ in clean], [n for _, n in clean]), 95
        )) if clean else None,
    }


def read(ctx, stat):
    loaded = spans_mod.for_cell(ctx)
    counted = reduce(spans_mod.whole(loaded["host"], loaded["window_ns"]))
    if counted is None:
        return None
    print(json.dumps({"tick_gaps": counted}), flush=True)
    return counted[stat]
