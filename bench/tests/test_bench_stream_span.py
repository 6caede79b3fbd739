"""The device-returning member call as the benchmark reads it: a traced
CPU rehearsal of a bulk cell, where ``Deployment.stream`` keeps its
result on the device, and the new reader on runs that have no such
span."""
import pytest

from bench import catalog
from bench.tests.test_bench_harness import CONTRACT_KEYS, harness  # noqa: F401

READER = "member.stream_ms_per_call.bulk"


def test_traced_bulk_run_reads_the_device_returning_call(
        harness, monkeypatch):  # noqa: F811
    real_reader, seen = catalog.reader, {}

    def reader(name, *a, **kw):
        read = real_reader(name, *a, **kw)

        def keep(ctx):
            seen["ctx"] = ctx
            return read(ctx)

        return keep

    monkeypatch.setattr(catalog, "reader", reader)
    rc, res, err = harness("deep-1t1m.bulk", trace=1)
    assert rc == 0, err
    assert set(res) <= CONTRACT_KEYS | {"breakdown"}
    assert res["correct"] is True
    got = res["metrics"]
    assert got[READER]["value"] > 0 and got[READER]["unit"] == "ms/call"
    parts = got["member.h2d_ms_per_call.bulk"]["value"] + \
        got["member.program_ms_per_call.bulk"]["value"]
    assert parts <= got[READER]["value"]
    ctx = seen["ctx"]
    counters, spans = ctx["obs"]["counters"], ctx["spans"]
    assert spans["fleet.stream"]["count"] > 0
    assert counters["fleet.h2d_bytes"] > 0
    assert "fleet.d2h_bytes" not in counters
    for name in ("fleet.d2h", "fleet.result_to_device",
                 "fleet.stream_host"):
        assert name not in spans


def test_stream_reader_finds_nothing_without_the_span():
    read = catalog.reader(READER)
    closed, open_ = {"loop": "closed"}, {"loop": "open"}
    calls = {"fleet.stream": {"count": 4, "seconds": 0.02}}
    assert read({"mix": closed, "spans": calls}) == pytest.approx(5.0)
    # the open loop, an untraced run, and a program without the span
    assert read({"mix": open_, "spans": calls}) is None
    assert read({"mix": closed, "spans": None}) is None
    assert read({"mix": closed, "spans": {
        "fleet.stream_host": {"count": 4, "seconds": 0.02}}}) is None
