"""The fleet member call's child spans as the benchmark reads them: a
traced CPU rehearsal of a bulk cell, the readers on a program that has
no such spans, and the trace reduction laying idle gaps on them,
worked out by hand."""
import pytest

from bench import catalog
from bench import trace_reduce as tr
from bench.tests.test_bench_harness import CONTRACT_KEYS, harness  # noqa: F401

CHILD_READERS = {"member.h2d_ms_per_call.bulk": "fleet.h2d",
                 "member.program_ms_per_call.bulk": "fleet.program"}
NEW_READERS = sorted(CHILD_READERS) + ["member.h2d_gbps.bulk"]
CALL_READER = "member.stream_ms_per_call.bulk"


def test_traced_bulk_run_reports_the_member_split(harness):  # noqa: F811
    rc, res, err = harness("deep-1t1m.bulk", trace=1)
    assert rc == 0, err
    assert set(res) <= CONTRACT_KEYS | {"breakdown"}
    assert res["correct"] is True
    got = res["metrics"]
    # the CPU has no device plane and no peaks: the device_trace and
    # peak readers find nothing, and their metrics are left out
    assert set(got) == {"setup.deploy_s", CALL_READER, *NEW_READERS}
    assert all(got[name]["value"] > 0 for name in NEW_READERS)
    assert got["member.h2d_gbps.bulk"]["unit"] == "GB/s"
    parts = sum(got[name]["value"] for name in CHILD_READERS)
    assert parts <= got[CALL_READER]["value"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_member_readers_find_nothing_without_the_spans(name):
    read = catalog.reader(name)
    closed, open_ = {"loop": "closed"}, {"loop": "open"}
    call = {"fleet.stream": {"count": 3, "seconds": 0.02}}
    # a program that opens only the whole call's span (and no counter)
    assert read({"mix": closed, "spans": call,
                 "obs": {"counters": {}}}) is None
    # an untraced run
    assert read({"mix": closed, "spans": None, "obs": None}) is None
    full = dict(call, **{s: {"count": 3, "seconds": 0.006}
                         for s in CHILD_READERS.values()})
    counters = {"counters": {"fleet.h2d_bytes": 3 * 12_845_056}}
    assert read({"mix": open_, "spans": full, "obs": counters}) is None
    value = read({"mix": closed, "spans": full, "obs": counters})
    if name == "member.h2d_gbps.bulk":
        assert value == pytest.approx(3 * 12_845_056 / 0.006 / 1e9)
    else:
        assert value == pytest.approx(2.0)


def test_idle_gaps_go_to_the_innermost_member_span():
    # slice [0, 10): the device runs 0-1, 4-6 and 9.2-10. The call
    # Deployment.stream (0-9.5) holds fleet.stream_host (0.5-9.0),
    # which holds the stage, h2d (1-4), program (4-6) and d2h (6-9);
    # the result upload follows (9-9.5).
    ops = {0: [("convert", 0.0, 1.0), ("fusion", 4.0, 6.0),
               ("copy", 9.2, 10.0)]}
    mods = {0: [("jit_stream", 4.0, 6.0)]}
    host = [("Deployment.stream", 0.0, 9.5),
            ("fleet.stream_host", 0.5, 9.0), ("fleet.stage", 0.5, 1.0),
            ("fleet.h2d", 1.0, 4.0), ("fleet.program", 4.0, 6.0),
            ("fleet.d2h", 6.0, 9.0), ("fleet.result_to_device", 9.0, 9.5)]
    red = tr.reduce_events((0.0, 10.0), ops, mods, host)
    assert red["busy_s"] == pytest.approx(3.8)
    assert red["idle_share"] == pytest.approx(0.62)
    # gap 1-4 (mid 2.5) lies in Deployment.stream, fleet.stream_host
    # and fleet.h2d: the innermost takes it; gap 6-9.2 (mid 7.6) is
    # fleet.d2h's, though it runs past the span's end
    assert dict(map(tuple, red["idle_gaps"])) == pytest.approx(
        {"fleet.h2d": 3.0, "fleet.d2h": 3.2})
