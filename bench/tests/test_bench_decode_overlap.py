"""The reader of ``lm.decode_overlap_pct`` on hand-made obs snapshots."""
import pytest

from bench import catalog


def _read(counters):
    return catalog.reader("lm.decode_overlap_pct")(
        {"obs": {"counters": counters}})


def test_share_of_the_steps_overlapped():
    # the window's first step found its predecessor done
    assert _read({"lm.decode_dispatched": 500,
                  "lm.decode_overlapped": 499}) == pytest.approx(99.8)


def test_no_step_overlapped_is_zero():
    assert _read({"lm.decode_dispatched": 3}) == 0.0


@pytest.mark.parametrize("counters", [{}, {"lm.decode_overlapped": 4},
                                      {"lm.decode_dispatched": 0}])
def test_nothing_to_read_is_none(counters):
    assert _read(counters) is None


def test_no_snapshot_is_none():
    assert catalog.reader("lm.decode_overlap_pct")({"obs": None}) is None
