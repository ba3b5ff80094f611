"""The one general generator of open-loop serving traffic.

A mix is a data file of parameters. What a run is asked to do is the
same for every seed: the count of arrivals, the multiset of
``(prompt, answer)`` lengths (quantiles of the file's two log-normal
distributions, paired by a permutation its ``pairing_seed`` fixes), their
order and their due times (one realisation of a Poisson process at the
file's rate, drawn from its ``schedule_seed``) are all fixed by the
file. The run's seed sets the token ids. A schedule drawn anew from
every seed was tried first and made the seed change the work: the
tokens delivered inside a 30 s window swung by 6% and the 90th
percentile of first-token time fourfold between seeds, where two runs of
one seed agreed within 0.3% and 1% (PERF.md, PR 24).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> np.ndarray:
    """``n`` lengths at the mid-point quantiles of a log-normal with
    the given median and log-space sigma, clipped to ``[lo, hi]``."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def length_pairs(mix: dict, n: int) -> np.ndarray:
    """The fixed multiset of ``n`` (prompt, answer) lengths."""
    p, a = mix["prompt"], mix["answer"]
    prompts = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    answers = _lognormal_quantiles(n, a["median"], a["sigma"], a["min"], a["max"])
    pairing = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    answers = answers[pairing]
    room = mix["max_total"] - prompts
    return np.stack([prompts, np.minimum(answers, room)], axis=1)


def arrivals(mix: dict, seconds: float, seed: int, vocab_size: int,
             *, stream: int = 0) -> list[dict]:
    """Requests due in ``[0, seconds)``: a Poisson process at the mix's
    fixed rate, conditioned on its count ``round(rate * seconds)`` (the
    due times are that many sorted uniform draws; with ``burst`` set,
    arrivals come in groups of that size at one instant). Order and
    times come from the file's ``schedule_seed``, token ids from
    ``seed``. ``stream`` separates the pre-roll's and the post-roll's
    draws from the window's."""
    n = int(round(mix["rate_per_s"] * seconds))
    schedule = np.random.default_rng([int(mix["schedule_seed"]), int(stream)])
    pairs = length_pairs(mix, n)[schedule.permutation(n)]
    burst = int(mix.get("burst", 1))
    groups = -(-n // burst)
    due = np.repeat(
        np.sort(schedule.uniform(0.0, seconds, size=groups)), burst
    )[:n]
    ids = np.random.default_rng([int(seed), 0x5EED, int(stream)])
    return [
        {"due": float(t), "max_new_tokens": int(alen),
         "prompt": ids.integers(0, vocab_size, size=int(plen), dtype=np.int32)}
        for (plen, alen), t in zip(pairs, due)
    ]
