"""The trace reduction, on events whose busy and idle time were worked
out by hand."""
import pytest

from bench import trace_reduce as tr


def test_union_clip_and_gaps_by_hand():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (6, 6)]) == \
        [(0, 2.5), (3, 4)]
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_reduce_events_by_hand():
    # slice [0, 10): device 0 busy 0-2 and 3-6 (overlapping ops), so
    # 5 s busy; device 1 busy 1-2, 1 s. Mean busy 3 s, idle 70%.
    ops = {0: [("fusion", 0.0, 2.0), ("copy", 1.0, 1.5),
               ("fusion", 3.0, 6.0), ("late", 9.5, 12.0)],
           1: [("fusion", 1.0, 2.0)]}
    mods = {0: [("jit_step", 0.0, 2.0), ("jit_step", 3.0, 6.0)],
            1: [("jit_step", 1.0, 2.0)]}
    host = [("router.step", 2.0, 3.0), ("bench.wait", 6.0, 9.0),
            ("router.step", 9.0, 10.0)]
    red = tr.reduce_events((0.0, 10.0), ops, mods, host)
    assert red["window_s"] == 10.0
    assert red["busy_s_each"] == [5.5, 1.0]
    assert red["busy_s"] == pytest.approx(3.25)
    assert red["idle_share"] == pytest.approx(0.675)
    assert red["modules"] == {"jit_step": {"calls": 3, "seconds": 6.0}}
    assert red["device_ops"][0] == ["fusion", 6.0]
    # device 0 gaps: 2-3 (router.step), 6-9.5 (mid 7.75: bench.wait);
    # device 1 gaps: 0-1 (none), 2-10 (mid 6: bench.wait)
    assert dict(map(tuple, red["idle_gaps"])) == {
        "bench.wait": 11.5, "router.step": 1.0, tr.NO_ANNOTATION: 1.0}
