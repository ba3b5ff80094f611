"""The traffic generator is seed-stable: every seed is asked for the
same work in another order."""

import json
import os

import numpy as np

from harness import manifest, traffic


def _mix():
    path = os.path.join(manifest.BENCH_DIR, "workloads", "gpt2m-serve.json")
    with open(path) as f:
        return json.load(f)["traffic"]


def test_every_seed_is_asked_for_the_same_work():
    mix = _mix()
    a = traffic.arrivals(mix, 30.0, 11, 50257)
    b = traffic.arrivals(mix, 30.0, 2_147_483_659, 50257)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)
    # The same count, lengths, order and due times; other token ids.
    assert [(len(r["prompt"]), r["max_new_tokens"], r["due"]) for r in a] == [
        (len(r["prompt"]), r["max_new_tokens"], r["due"]) for r in b]
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))


def test_the_schedule_is_the_files_own_draw():
    mix = _mix()
    a = traffic.arrivals(mix, 30.0, 11, 50257)
    other = traffic.arrivals(dict(mix, schedule_seed=8), 30.0, 11, 50257)

    def pairs(reqs):
        return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)

    assert pairs(a) == pairs(other)  # the multiset stays, the order goes
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in other]
    assert [r["due"] for r in a] != [r["due"] for r in other]
    # The pre-roll and the post-roll draw apart from the window.
    assert [r["due"] for r in traffic.arrivals(mix, 30.0, 11, 50257, stream=1)] \
        != [r["due"] for r in a]


def test_same_seed_same_inputs_and_limits_hold():
    mix = _mix()
    a = traffic.arrivals(mix, 30.0, 5, 50257)
    b = traffic.arrivals(mix, 30.0, 5, 50257)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["due"] == y["due"]
               for x, y in zip(a, b))
    dues = [r["due"] for r in a]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] < 30.0
    for r in a:
        n = len(r["prompt"])
        assert mix["prompt"]["min"] <= n <= mix["prompt"]["max"]
        assert 1 <= r["max_new_tokens"] <= mix["answer"]["max"]
        assert n + r["max_new_tokens"] <= mix["max_total"]
        assert r["prompt"].min() >= 0 and r["prompt"].max() < 50257


def test_bursts_share_an_instant():
    mix = dict(_mix(), burst=16)
    reqs = traffic.arrivals(mix, 30.0, 3, 50257)
    dues = [r["due"] for r in reqs]
    assert len(set(dues)) == -(-len(reqs) // 16)
