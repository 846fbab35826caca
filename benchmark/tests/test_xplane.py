"""The trace reduction: on hand-made events with known answers, and on a
small trace recorded on the chip (tests/data/small.xplane.pb, made by
tools/record_small_trace.py: three rounds of jit `alpha`, a 20 ms sleep
under a `bench.pause` span, jit `beta`)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import xplane  # noqa: E402

SMALL = os.path.join(HERE, "data", "small.xplane.pb")
MS = 1_000_000   # ns


def hand_made():
    dev = "/device:TPU:0"
    t = xplane.Trace()
    # two programs; ops overlap inside the first; 30 ms idle between
    t.modules[dev] = [("jit_a(1)", 10 * MS, 30 * MS),
                      ("jit_b(2)", 60 * MS, 70 * MS)]
    t.ops[dev] = [("fusion.1", 10 * MS, 20 * MS),
                  ("kern", 15 * MS, 30 * MS),      # overlaps fusion.1
                  ("kern", 60 * MS, 64 * MS),
                  ("fusion.2", 66 * MS, 70 * MS)]
    t.host = [("main", "bench.window", 0, 100 * MS),
              ("main", "bench.step", 5 * MS, 58 * MS),
              ("main", "bench.pause", 31 * MS, 57 * MS),
              ("other", "SomeRuntimeThing", 0, 100 * MS)]
    return t


def test_busy_is_the_union_not_the_sum():
    t = hand_made()
    window = xplane.window_of(t)
    assert window == (0, 100 * MS)
    # 10-30 and 60-64 and 66-70: 28 ms, though the op durations sum to 33
    assert xplane.busy_seconds(t, window) == pytest.approx(0.028)
    # clipped to a window that cuts the first program in half
    assert xplane.busy_seconds(t, (20 * MS, 62 * MS)) == pytest.approx(0.012)


def test_per_program_and_per_kernel_time():
    t = hand_made()
    s = xplane.summarize(t)
    assert s["programs"]["jit_a"] == {"count": 1, "total_s": 0.02,
                                      "median_s": 0.02}
    assert s["programs"]["jit_b"]["total_s"] == pytest.approx(0.01)
    assert s["kernels"]["kern"]["count"] == 2
    assert s["kernels"]["kern"]["total_s"] == pytest.approx(0.019)
    inside = s["kernels_in_programs"]
    assert inside["jit_a"]["kern"]["total_s"] == pytest.approx(0.015)
    assert inside["jit_b"]["kern"]["total_s"] == pytest.approx(0.004)
    assert s["program_gaps_s"] == [pytest.approx(0.03)]
    assert s["device_ops"][0][0] == "kern"


def test_gaps_go_to_the_innermost_own_span_that_covers_them():
    s = xplane.summarize(hand_made())
    gaps = dict(s["idle_gaps"])
    # 30-60 ms: bench.pause covers 26 of 30 ms and is inside bench.step
    assert gaps["bench.pause"] == pytest.approx(0.03)
    # 0-10 ms: bench.step covers half of it; 64-66 and 70-100: no own span
    assert gaps["bench.step"] == pytest.approx(0.01)
    assert gaps["SomeRuntimeThing"] == pytest.approx(0.032)
    assert sum(gaps.values()) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_a_trace_with_no_device_plane_reads_nothing():
    t = xplane.Trace()
    t.host = [("main", "bench.window", 0, 10 * MS)]
    s = xplane.summarize(t)
    assert s["busy_s"] == 0.0 and s["programs"] == {}
    assert s["device_ops"] == [] and s["idle_gaps"] == []


@pytest.mark.skipif(not os.path.isfile(SMALL), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    t = xplane.load(SMALL)
    assert list(t.ops) == ["/device:TPU:0"]
    s = xplane.summarize(t)
    assert 0.06 < s["window_s"] < 5.0
    assert 0.0 < s["busy_s"] < s["window_s"]
    # another way to the same number: sweep the sorted endpoints and add
    # up the stretches in which at least one operation is open
    t0, t1 = s["window"]
    points = []
    for _n, a, b in t.ops["/device:TPU:0"]:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            points += [(a, 1), (b, -1)]
    open_ops, last, busy = 0, None, 0.0
    for at, step in sorted(points):
        if open_ops > 0:
            busy += at - last
        open_ops, last = open_ops + step, at
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 20e-6 < s["busy_s"] < 200e-6     # six programs of ~8-16 us
    # the device's clock runs some tenths of a millisecond ahead of the
    # host's in this trace: the first alpha is stamped 26 us BEFORE the
    # window span that was opened before it was launched, and falls out
    assert s["programs"]["jit_alpha"]["count"] == 2
    assert s["programs"]["jit_beta"]["count"] == 3
    assert len(s["program_gaps_s"]) == 4
    assert set(s["kernels_in_programs"]) == {"jit_alpha", "jit_beta"}
    gaps = dict(s["idle_gaps"])
    assert 0.055 < gaps["bench.pause"] < 0.08      # three 20 ms sleeps
    assert max(gaps, key=gaps.get) == "bench.pause"
