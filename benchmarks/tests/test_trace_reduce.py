import os

import pytest

from benchmarks import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_gaps_on_hand_made_intervals():
    iv = [(0.0, 1.0), (0.5, 1.0), (3.0, 0.5), (3.1, 0.1)]
    assert trace_reduce.union_s(iv) == pytest.approx(2.0)
    g = trace_reduce.gaps(iv, 0.0, 4.0)
    assert g == [pytest.approx((1.5, 1.5)), pytest.approx((3.5, 0.5))]


def test_gaps_are_named_by_the_host_dispatch_they_fall_in():
    reduced = {"devices": [{"ops": [("a", 10.0, 1.0), ("b", 12.0, 1.0)], "modules": []}],
               "window_s": 3.0, "busy_s": 2.0, "lo": 10.0, "hi": 13.0,
               "mark_trace_s": 10.0, "mark_host_s": 110.0}
    flight = [{"kind": "dispatch", "name": "dispatch.step", "ts": 110.9,
               "issue_s": 1.2, "sync_s": 0.1, "tags": {}}]
    bd = trace_reduce.breakdown(reduced, flight, 110.0, 113.0)
    assert bd["device_ops"][0][1] == pytest.approx(1.0)
    assert bd["idle_gaps"] == [["dispatch.step:issue", pytest.approx(1.0)]]


@pytest.mark.skipif(not os.path.isdir(os.path.join(DATA, "small_trace")),
                    reason="no recorded trace")
def test_the_recorded_chip_trace_reduces_to_its_known_numbers():
    import json

    with open(os.path.join(DATA, "small_trace.expected.json"), encoding="utf-8") as f:
        want = json.load(f)
    got = trace_reduce.reduce_dir(os.path.join(DATA, "small_trace"), 1)
    assert got["mark_trace_s"] is not None
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    times = trace_reduce.op_times(got, "modules")
    assert max(times, key=times.get) == want["top_module"]
