"""`trace_reduce` on a small recorded reduction, against numbers worked
out by hand (times in seconds).

Device 0 runs a: [1.0, 2.0], b: [1.5, 2.5] (overlaps a), a: [4.0, 4.5];
the window is the benchmark's annotation [0.5, 5.0]. Busy union =
[1.0, 2.5] + [4.0, 4.5] = 2.0 s of 4.5 s, idle share 2.5/4.5. Per-op
time: a = 1.0 + 0.5 = 1.5, b = 1.0. Gaps: [0.5, 1.0] under host span
`encode` ([0.4, 1.2]); [2.5, 4.0] under the innermost open span
`dispatch` ([2.4, 3.0], inside `sweep` [2.0, 4.2]); [4.5, 5.0] under
nothing -> `host-idle`.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402


def _recorded():
    with open(os.path.join(os.path.dirname(HERE), "testdata",
                           "trace_small.json")) as fh:
        rec = json.load(fh)
    ops = {int(k): [tuple(e) for e in v]
           for k, v in rec["device_ops"].items()}
    return ops, [tuple(h) for h in rec["host"]]


def test_union_and_gaps():
    assert trace_reduce.union([(1, 2), (1.5, 2.5), (4, 4.5)]) == [
        (1, 2.5), (4, 4.5)]
    assert trace_reduce.gaps([(1, 2.5), (4, 4.5)], (0.5, 5.0)) == [
        (0.5, 1), (2.5, 4), (4.5, 5.0)]


def test_reduction_matches_hand_count():
    ops, host = _recorded()
    red = trace_reduce.reduce_events(ops, host, n_devices=1)
    assert abs(red["busy_s"] - 2.0) < 1e-12
    assert abs(red["window_s"] - 4.5) < 1e-12
    assert abs(red["idle_share"] - 2.5 / 4.5) < 1e-12
    assert red["device_ops"] == [["a", 1.5], ["b", 1.0]]
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert abs(gaps["dispatch"] - 1.5) < 1e-12
    assert abs(gaps["encode"] - 0.5) < 1e-12
    assert abs(gaps["host-idle"] - 0.5) < 1e-12
    assert red["n_ops"] == 3


def test_busy_is_averaged_over_devices():
    ops, host = _recorded()
    ops[1] = []                       # a second chip that ran nothing
    red = trace_reduce.reduce_events(ops, host, n_devices=2)
    assert abs(red["busy_s"] - 1.0) < 1e-12


def test_no_events_gives_no_share():
    red = trace_reduce.reduce_events({}, [], n_devices=1)
    assert red["idle_share"] is None and red["n_ops"] == 0
