"""The benchmark's trace reduction on small hand-built traces."""

import pytest

from benchmark import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6  # ns


def ev(plane, line, name, start_ms, dur_ms):
    return tr.Event(plane, line, name, start_ms * MS, dur_ms * MS)


def window(lo_ms, hi_ms):
    return ev(HOST, "python3", "bench.window", lo_ms, hi_ms - lo_ms)


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),          # overlap
    ([(0, 4), (1, 2)], [(0, 4)]),          # nested
    ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),  # out of order, disjoint
    ([(0, 1), (1, 2)], [(0, 2)]),          # touching
    ([(5, 5), (0, 1)], [(0, 1)]),          # empty interval dropped
])
def test_union(intervals, merged):
    assert tr.union(intervals) == merged


def test_busy_union_counts_nested_ops_once():
    events = [
        window(0, 100),
        ev(DEV, tr.OPS_LINE, "fusion.1", 10, 20),
        ev(DEV, tr.OPS_LINE, "fusion.2", 15, 10),     # inside fusion.1
        ev(DEV, tr.OPS_LINE, "while.3", 60, 30),
        ev(DEV, tr.MODULES_LINE, "jit__detect_batch_wire(1)", 10, 80),
        ev(DEV, "Steps", "step 0", 0, 100),            # not an op line
    ]
    red = tr.reduce(events)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.050)       # 10-30 and 60-90
    assert red["tail_idle_s"] == pytest.approx(0.010)  # 90-100
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.010)     # less fusion.2
    assert ops["fusion.2"] == pytest.approx(0.010)
    assert ops["while.3"] == pytest.approx(0.030)
    assert "step 0" not in ops
    assert tr.module_seconds(red, "_detect_batch_wire") == \
        pytest.approx(0.080)
    assert tr.module_seconds(red, "pack_egress") is None


def test_idle_gaps_named_by_the_overlapping_bench_span():
    events = [
        window(0, 100),
        ev(DEV, tr.OPS_LINE, "a", 10, 10),
        ev(DEV, tr.OPS_LINE, "b", 50, 40),
        ev(HOST, "writer", "bench.store.write", 22, 26),   # covers 20-50
        ev(HOST, "fetch", "bench.source.chip", 0, 5),      # part of 0-10
    ]
    red = tr.reduce(events)
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.store.write", "bench.source.chip",
                                   "bench.window"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.010, 0.010])
    assert sum(red["idle_by_span_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_ops_outside_the_window_are_clipped():
    events = [
        window(10, 20),
        ev(DEV, tr.OPS_LINE, "early", 0, 12),    # 10-12 inside
        ev(DEV, tr.OPS_LINE, "late", 18, 10),    # 18-20 inside
        ev(DEV, tr.OPS_LINE, "outside", 30, 5),
    ]
    red = tr.reduce(events)
    assert red["busy_s"] == pytest.approx(0.004)
    assert "outside" not in dict(red["device_ops"])


def test_busy_is_averaged_over_device_planes():
    dev1 = "/device:TPU:1"
    events = [
        window(0, 100),
        ev(DEV, tr.OPS_LINE, "x", 0, 100),
        ev(dev1, tr.OPS_LINE, "x", 0, 50),
    ]
    red = tr.reduce(events)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.075)


def test_self_time_of_nested_ops_and_short_names():
    events = [
        window(0, 100),
        ev(DEV, tr.OPS_LINE, "%while.5 = (f32[4,8]) while(...)", 0, 50),
        ev(DEV, tr.OPS_LINE, "%fusion.7 = f32[4,8] fusion(...)", 5, 10),
        ev(DEV, tr.OPS_LINE, "%fusion.8 = f32[4,8] fusion(...)", 20, 10),
        ev(DEV, tr.OPS_LINE, "%copy.9 = f32[4,8] copy(...)", 22, 4),
        ev(DEV, tr.OPS_LINE, "%fusion.7 = f32[4,8] fusion(...)", 60, 5),
    ]
    ops = dict(tr.reduce(events)["device_ops"])
    assert ops == pytest.approx({"%while.5": 0.030, "%fusion.7": 0.015,
                                 "%fusion.8": 0.006, "%copy.9": 0.004})


def test_plane_without_an_ops_line_uses_all_its_events():
    events = [window(0, 10), ev(DEV, "TensorCore", "k", 0, 5)]
    assert tr.reduce(events)["busy_s"] == pytest.approx(0.005)


def test_no_device_plane_and_no_window():
    assert tr.reduce([window(0, 10)])["devices"] == 0
    with pytest.raises(ValueError):
        tr.reduce([ev(DEV, tr.OPS_LINE, "x", 0, 1)])
