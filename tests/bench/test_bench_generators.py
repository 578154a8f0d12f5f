"""The chip benchmark's seeded event and scenario generators."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks"
                       / "chip"))

from psbench import events  # noqa: E402

MIX = dict(rate_hz=20.0, burst_mean=4.0, degrade_share=0.05,
           degrade_scale=(0.3, 0.8), restore_after_s=5.0)


def stream(seed, seconds=30.0, n=500, k=32, timeline=20111):
    return events.churn_stream(
        n, k, seconds, timeline=np.random.default_rng(timeline),
        pick=np.random.default_rng([1, seed]), **MIX)


def test_one_seed_gives_one_stream():
    assert stream(2**31 + 11) == stream(2**31 + 11)
    assert stream(2**31 + 11) != stream(2**31 + 12)


def test_seeds_share_the_timeline_and_differ_in_whom_it_hits():
    a, b = stream(2**31 + 11), stream(2**31 + 12)
    shape = [(e.due, e.kind, e.scale) for e in a]
    assert shape == [(e.due, e.kind, e.scale) for e in b]
    assert [e.user for e in a] != [e.user for e in b]
    other = stream(2**31 + 11, timeline=7)
    assert [e.due for e in other] != [e.due for e in a]


def test_stream_is_sorted_in_window_and_near_its_rate():
    evs = stream(3, seconds=200.0)
    due = [e.due for e in evs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 200.0
    assert 0.85 * 4000 < len(evs) < 1.15 * 4000
    degrades = sum(e.kind == "degrade" for e in evs)
    assert 0.5 * 100 < degrades < 1.5 * 100


def test_bursts_and_tenant_states_are_consistent():
    evs = stream(4, seconds=60.0, n=50)
    present = np.ones(50, bool)
    degraded = set()
    same_time = {}
    for e in evs:
        if e.kind == "departure":
            assert present[e.user]
            present[e.user] = False
            assert present.any()
        elif e.kind == "arrival":
            assert not present[e.user]      # only returning slots
            present[e.user] = True
        elif e.kind == "degrade":
            assert e.server not in degraded and 0.3 <= e.scale <= 0.8
            degraded.add(e.server)
        else:
            assert e.server in degraded
            degraded.discard(e.server)
        if e.kind in ("arrival", "departure"):
            same_time[e.due] = same_time.get(e.due, 0) + 1
    sizes = list(same_time.values())
    assert 2.5 < np.mean(sizes) < 5.5 and max(sizes) > 4

