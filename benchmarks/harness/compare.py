"""The comparison that decides ``correct``: each number read beside a
limit of its own."""

from __future__ import annotations

import sys

import numpy as np


def worst_leaf_gap(got: dict, want: dict) -> float:
    """Over every leaf (the stacked arrays layer by layer): the gap
    between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    return float(np.max(_leaf_gaps(got, want)))


def median_leaf_gap(got: dict, want: dict) -> float:
    """The same gaps, the median leaf's: for a number that one small
    leaf's noise decides (Adam steps a leaf whose gradient is rounding
    noise by a full rate, whichever way the noise points)."""
    return float(np.median(_leaf_gaps(got, want)))


def gap_quantiles(got: dict, want: dict) -> dict:
    """Median, 90th percentile and widest of the per-leaf gaps."""
    gaps = _leaf_gaps(got, want)
    return {"median": float(np.median(gaps)),
            "p90": float(np.percentile(gaps, 90)), "max": float(np.max(gaps))}


def worst_leaves(got: dict, want: dict, k: int = 3) -> list:
    """``[(leaf, gap)]`` of the ``k`` widest gaps, stacked arrays by
    layer (``layers/wq[5]``): what a reading far off is traced to."""
    names = []
    for key in sorted(want):
        n = np.size(want[key])
        names += [key] if np.ndim(want[key]) == 0 else [
            f"{key}[{i}]" for i in range(n)]
    gaps = _leaf_gaps(got, want)
    order = np.argsort(-gaps)[:k]
    return [(names[i], float(gaps[i])) for i in order]


def _leaf_gaps(got: dict, want: dict) -> np.ndarray:
    missing = set(want) ^ set(got)
    if missing:
        raise KeyError(f"leaves on one side only: {sorted(missing)[:6]}")
    ref = np.concatenate([np.ravel(np.asarray(want[k], np.float64))
                          for k in sorted(want)])
    prog = np.concatenate([np.ravel(np.asarray(got[k], np.float64))
                           for k in sorted(want)])
    scale = np.maximum(ref, np.median(ref))
    scale = np.where(scale > 0, scale, 1.0)
    return np.abs(prog - ref) / scale


class Comparison:
    """Numbers compared, each with its limit; ``correct`` is all of
    them inside."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            np.isfinite(v) and v <= lim for _, v, lim in self.rows
        )

    def as_dict(self) -> dict:
        return {name: {"value": v, "limit": lim} for name, v, lim in self.rows}

    def print_last_lines(self) -> None:
        for name, v, lim in self.rows:
            verdict = "ok" if np.isfinite(v) and v <= lim else "OVER"
            print(f"compared {name} = {v:.6g} limit {lim:.6g} {verdict}",
                  file=sys.stderr, flush=True)
