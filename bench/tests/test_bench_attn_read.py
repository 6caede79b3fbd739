"""The reader of ``lm.attn_read_pct`` on hand-made obs snapshots."""
import pytest

from bench import catalog


def _read(counters):
    return catalog.reader("lm.attn_read_pct")({"obs": {"counters": counters}})


def test_share_of_the_ring_read():
    # five 512-slot blocks of a 4096-slot ring, over three steps
    assert _read({"lm.attn_slots_read": 3 * 2560,
                  "lm.attn_slots_ring": 3 * 4096}) == pytest.approx(62.5)


@pytest.mark.parametrize("counters", [{}, {"lm.attn_slots_read": 512},
                                      {"lm.attn_slots_ring": 0}])
def test_nothing_to_read_is_none(counters):
    assert _read(counters) is None


def test_no_snapshot_is_none():
    assert catalog.reader("lm.attn_read_pct")({"obs": None}) is None
