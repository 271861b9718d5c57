"""The reduction from a trace to busy time, idle share, collective time
and the breakdown, on small traces whose answers are counted by hand."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import tracereduce  # noqa: E402

# Two devices over a 600 ns window. Device 0: two overlapping fusions
# (busy 0-150), an all-reduce (200-250), a copy (400-500). Device 1: a
# fusion begun before the window (busy 0-200 inside it), an all-reduce
# (300-400).
HAND = {
    "devices": {
        "/device:TPU:0": [["fusion.1", 0, 100], ["fusion.2", 50, 100],
                          ["all-reduce.3", 200, 50], ["copy.4", 400, 100]],
        "/device:TPU:1": [["fusion.1", -100, 300],
                          ["all-reduce.3", 300, 100]],
    },
    "host": [["bench.window_fit", 0, 600],
             ["bench.chunk_dispatch", 300, 100],
             ["bench.chunk_dispatch", 550, 10]],
}


def test_union_and_clip():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert tracereduce.clip([(-5, 5), (8, 20), (30, 40)], 0, 10) == [
        (0, 5), (8, 10)]


def test_hand_counted_trace():
    got = tracereduce.reduce_events(HAND, (0.0, 600.0))
    assert got["n_devices"] == 2
    assert got["window_s"] == pytest.approx(600e-9)
    # device 0 busy 150 + 50 + 100 = 300 ns, device 1 200 + 100 = 300 ns
    assert got["busy_s"] == pytest.approx(300e-9)
    assert got["idle_pct"] == pytest.approx(50.0)
    # all-reduce: 50 ns and 100 ns, averaged over the two devices
    assert got["collective_s"] == pytest.approx(75e-9)
    ops = dict(got["breakdown"]["device_ops"])
    # fusion.1: 100 ns on device 0 and 200 ns inside the window on 1
    assert ops["fusion.1"] == pytest.approx(150e-9)
    assert ops["all-reduce.3"] == pytest.approx(75e-9)
    # device 0's gaps: 150-200, 250-400 (the middle, 325, falls in a
    # chunk dispatch), 500-600 (550 is in the second dispatch)
    assert got["breakdown"]["idle_gaps"] == [
        ["bench.chunk_dispatch", pytest.approx(150e-9)],
        ["bench.chunk_dispatch", pytest.approx(100e-9)],
        ["bench.window_fit", pytest.approx(50e-9)]]


def test_host_window_and_empty_cases():
    assert tracereduce.host_window(HAND, "bench.chunk_dispatch") == (
        300, 560)
    assert tracereduce.host_window(HAND, "bench.absent") is None
    with pytest.raises(ValueError):
        tracereduce.reduce_events(HAND, (10.0, 10.0))
    with pytest.raises(ValueError):
        tracereduce.reduce_events({"devices": {}, "host": []}, (0.0, 1.0))


def _busy_by_grid(ops, lo, hi, step):
    """Busy time counted on a grid of ``step`` ns: an independent count
    of the union for the recorded trace."""
    n = int((hi - lo) // step)
    hit = [False] * n
    for _, s, d in ops:
        first = max(0, int((s - lo) // step))
        last = min(n, int((s + d - lo) // step))
        for i in range(first, last):
            hit[i] = True
    return sum(hit) * step


@pytest.mark.parametrize("name", sorted(
    p.name for p in (HERE / "data").glob("trace_*.json")))
def test_recorded_trace(name):
    """Small traces recorded with the profiler (``tests/data/``; each
    file's ``source`` says where): the reduction's busy time agrees with
    an independent count on a 1 us grid."""
    events = json.loads((HERE / "data" / name).read_text())
    window = tuple(events["window"])
    got = tracereduce.reduce_events(events, window)
    ops = next(iter(events["devices"].values()))
    lo, hi = window
    busy_grid = _busy_by_grid(ops, lo, hi, 1000.0)
    assert got["busy_s"] == pytest.approx(busy_grid * 1e-9, rel=0.02)
    assert 0.0 <= got["idle_pct"] <= 100.0
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    names = [name for name, _ in got["breakdown"]["device_ops"]]
    assert len(names) == len(set(names)) <= 10
    assert got["breakdown"]["device_ops"] == sorted(
        got["breakdown"]["device_ops"], key=lambda kv: -kv[1])


def test_compilation_inside_the_window_is_left_out():
    """Leaving out 400-500 (device 0's copy, nothing on device 1) takes
    100 ns from the window and from device 0's busy time."""
    got = tracereduce.reduce_events(HAND, (0.0, 600.0),
                                    exclude=[(400.0, 500.0)])
    assert got["window_s"] == pytest.approx(500e-9)
    assert got["busy_s"] == pytest.approx(250e-9)
    assert got["idle_pct"] == pytest.approx(50.0)
    assert ["compilation", pytest.approx(150e-9)] not in \
        got["breakdown"]["idle_gaps"]
    got = tracereduce.reduce_events(HAND, (0.0, 600.0),
                                    exclude=[(260.0, 300.0)])
    assert got["breakdown"]["idle_gaps"][0] == [
        "compilation", pytest.approx(150e-9)]
