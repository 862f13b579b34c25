"""The reduction from a profiler trace to busy time, idle gaps, per-op time
and collective time, on a hand-made event list and on a small trace
recorded on a TPU v5e and kept with these tests."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    ops = [["fusion.1", 0, 10], ["all-reduce.1", 10, 20], ["fusion.2", 15, 25],
           ["fusion.3", 40, 50]]
    return {"device": {"0": ops, "1": [list(o) for o in ops]},
            "spans": [["stitch_call", 0, 30], ["stitch_call", 35, 55]]}


def test_reduce_hand_made():
    r = trace.reduce(hand_made())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(55 * ns)
    assert r["busy_s"] == pytest.approx(35 * ns)
    assert r["chips"] == 2
    assert dict((k, v) for k, v in r["ops"]) == pytest.approx(
        {"fusion.1": 10 * ns, "all-reduce.1": 10 * ns, "fusion.2": 10 * ns,
         "fusion.3": 10 * ns})
    assert r["collective_s"] == pytest.approx(10 * ns)
    assert r["exposed_collective_s"] == pytest.approx(5 * ns)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"outside any span": 15 * ns, "stitch_call": 5 * ns})
    assert [b for _, b in r["span_busy_s"]] == pytest.approx([25 * ns, 10 * ns])


def test_nested_ops_count_their_own_time():
    ops = [["while", 0, 100], ["fusion.a", 10, 30], ["fusion.b", 40, 50],
           ["copy", 120, 130]]
    assert dict(trace.self_times(ops)) == {"while": 70, "fusion.a": 20,
                                           "fusion.b": 10, "copy": 10}
    r = trace.reduce({"device": {"0": ops}, "spans": [["tick", 0, 130]]})
    assert dict(r["ops"])["while"] == pytest.approx(70e-9)
    assert r["busy_s"] == pytest.approx(110e-9)


def test_reduce_window_and_empty():
    ev = hand_made()
    r = trace.reduce(ev, (35, 55))
    assert r["busy_s"] == pytest.approx(10e-9)
    assert r["window_s"] == pytest.approx(20e-9)
    assert trace.reduce(ev, (60, 70)) is None
    assert trace.reduce({"device": {}, "spans": []}) is None


def test_union_overlap():
    u = trace.Union([(5, 7), (0, 2), (1, 3), (10, 12)])
    assert u.iv == [(0, 3), (5, 7), (10, 12)]
    assert u.overlap(2, 11) == pytest.approx(1 + 2 + 1)
    assert u.overlap(-5, 100) == pytest.approx(7)
    assert u.overlap(3, 5) == 0


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".json")) if os.path.isdir(DATA) else [])
def test_reduce_recorded_trace(name):
    """A trace recorded on the chip: every stitched call or tick holds
    device time, busy never exceeds the window, and the breakdown lists
    the operations that took most time first."""
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    ev, want = rec["events"], rec["expected"]
    r = trace.reduce(ev)
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    times = [t for _, t in r["ops"]]
    assert times == sorted(times, reverse=True)
    assert all(b > 0 for _, b in r["span_busy_s"])
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
