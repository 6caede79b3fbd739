"""repro.obs: unified telemetry — metrics registry, span tracer,
step-phase profiling.

Covers the PR-8 acceptance surface:

  * bounded-reservoir histograms: exact values (and therefore exact
    percentiles) up to the cap, exact count/sum/min/max and bounded
    memory past it, deterministic retained sample set run-to-run;
  * registry semantics — label-key encoding, disabled registry is the
    shared no-op instrument, cross-host snapshot merge rules
    (counters add, gauges max, histogram reservoirs merge bounded);
  * Chrome trace schema — every complete span carries pid/tid/ts/dur,
    phase spans nest inside their step span, async request begin/end
    events pair up, the buffer is bounded and reports drops;
  * the instrumented keyed scheduler: phase spans tile each step,
    counters equal the scheduler's own accounting, the traced and
    untraced step paths emit identical results;
  * ``t_submit`` is stamped BEFORE the admission check (a rejected
    request still carries it) and rejects are counted per key;
  * ``stats_from_states`` off the reservoirs is IDENTICAL to the
    historic raw-list path for runs shorter than the reservoir;
  * a seeded 2-simulated-device subprocess serve produces identical
    counter values run-to-run.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.obs import (LANE_TID_BASE, MetricsRegistry, Reservoir,
                       Tracer, merge_snapshots)
from repro.serving.engine import (ItemRequest, KeyedItemStreamScheduler,
                                  StreamSpec)
from repro.serving.stats import stats_from_states


# ------------------------------------------------------------------- #
# reservoir + registry
# ------------------------------------------------------------------- #
def test_reservoir_exact_under_cap():
    r = Reservoir(cap=64)
    xs = np.random.default_rng(0).uniform(0, 9, 50)
    for x in xs:
        r.add(float(x))
    assert not r.saturated
    assert np.array_equal(np.sort(r.values), np.sort(xs))
    for q in (50, 95, 99):
        assert r.percentile(q) == float(np.percentile(xs, q))


def test_reservoir_bounded_with_exact_aggregates():
    r = Reservoir(cap=32)
    xs = np.random.default_rng(1).uniform(-3, 7, 1000)
    for x in xs:
        r.add(float(x))
    assert r.saturated and r.values.size == 32
    assert r.count == 1000
    assert r.total == pytest.approx(xs.sum())
    assert r.vmin == xs.min() and r.vmax == xs.max()
    assert r.mean == pytest.approx(xs.mean())
    # retained samples are a subset of what went in
    assert set(np.round(r.values, 12)) <= set(np.round(xs, 12))


def test_reservoir_deterministic_run_to_run():
    xs = np.random.default_rng(2).uniform(0, 1, 500)
    a, b = Reservoir(cap=16), Reservoir(cap=16)
    for x in xs:
        a.add(float(x))
        b.add(float(x))
    assert np.array_equal(a.values, b.values)


def test_registry_label_encoding_and_snapshot():
    m = MetricsRegistry()
    m.counter("engine.items").inc(3)
    m.counter("engine.items").inc(2)
    # labels are sorted into the key, insertion order irrelevant
    m.counter("engine.rejected", key="beta", host=0).inc()
    m.counter("engine.rejected", host=0, key="beta").inc()
    m.gauge("engine.lanes").set(6)
    h = m.histogram("request.latency_s")
    for v in (0.1, 0.2, 0.3):
        h.record(v)
    snap = m.snapshot()
    assert snap["counters"]["engine.items"] == 5
    assert snap["counters"]["engine.rejected|host=0,key=beta"] == 2
    assert snap["gauges"]["engine.lanes"] == 6.0
    hs = snap["histograms"]["request.latency_s"]
    assert hs["count"] == 3 and hs["p50"] == pytest.approx(0.2)


def test_disabled_registry_is_inert():
    m = MetricsRegistry(enabled=False)
    c = m.counter("x")
    c.inc(10)
    m.gauge("y").set(1.0)
    m.histogram("z").record(5.0)
    assert m.counter("other") is c          # one shared no-op object
    snap = m.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_merge_snapshots_counters_add_gauges_max_histograms_bound():
    a, b = MetricsRegistry(reservoir=8), MetricsRegistry(reservoir=8)
    for m, n, g in ((a, 3, 5.0), (b, 4, 9.0)):
        m.counter("steps").inc(n)
        m.gauge("lanes").set(g)
        for v in range(10):
            m.histogram("lat").record(float(v) + g)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["steps"] == 7
    assert merged["gauges"]["lanes"] == 9.0
    h = merged["histograms"]["lat"]
    assert h["count"] == 20
    assert h["min"] == 5.0 and h["max"] == 18.0
    assert len(h["values"]) <= h["cap"] == 8


# ------------------------------------------------------------------- #
# tracer
# ------------------------------------------------------------------- #
def test_tracer_complete_span_schema(tmp_path):
    tr = Tracer(pid=7)
    t0 = 0.0
    tr.complete("engine.step", t0, 0.010, cat="step",
                args={"emitted": 4})
    tr.complete("device_step", t0 + 0.001, 0.008, cat="phase")
    tr.instant("ha.takeover", args={"rank": 0})
    tr.async_span("request", 42, t0, t0 + 0.02,
                  args={"uid": 42})
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    for e in [e for e in evs if e["ph"] == "X"]:
        assert isinstance(e["pid"], int) and e["pid"] == 7
        assert isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["name"]
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    assert [e["id"] for e in begins] == [e["id"] for e in ends] == ["42"]
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in evs)
    # phase nests inside the step on the same track
    step, = [e for e in evs if e.get("cat") == "step"]
    ph, = [e for e in evs if e.get("cat") == "phase"]
    assert step["tid"] == ph["tid"] == 0
    assert step["ts"] <= ph["ts"]
    assert ph["ts"] + ph["dur"] <= step["ts"] + step["dur"] + 1e-9


def test_tracer_buffer_is_bounded():
    tr = Tracer(max_events=5)
    for i in range(9):
        tr.instant(f"e{i}")
    assert len(tr.trace_events()) == 5
    assert tr.dropped == 4
    assert tr.to_dict()["otherData"]["dropped_events"] == 4


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.complete("x", 0.0, 1.0)
    tr.instant("y")
    assert tr.trace_events() == []


# ------------------------------------------------------------------- #
# instrumented keyed scheduler
# ------------------------------------------------------------------- #
class _EchoScheduler(KeyedItemStreamScheduler):
    GAINS = {"a": 2.0, "b": -3.0}

    def _stream_batch_key(self, key, batch):
        return batch * self.GAINS[key]


def _echo(**kw):
    return _EchoScheduler({
        "a": StreamSpec(d_in=3, lanes=2, queue_limit=None),
        "b": StreamSpec(d_in=5, lanes=1, queue_limit=2),
    }, **kw)


@pytest.fixture
def tel():
    t = obs.configure()
    yield t
    obs.disable()


def _drive(eng, n_a=4, n_b=3):
    uid = 0
    for _ in range(n_a):
        eng.submit(ItemRequest(uid=uid, items=np.ones((2, 3)), key="a"))
        uid += 1
    for _ in range(n_b):
        if eng.submit(ItemRequest(uid=uid, items=np.ones((1, 5)),
                                  key="b")):
            uid += 1
    return eng.run_until_drained()


def test_scheduler_counters_match_accounting(tel):
    eng = _echo()
    done = _drive(eng)
    c = tel.metrics.snapshot()["counters"]
    assert c["engine.items"] == eng.items_emitted
    assert c["engine.steps"] == eng.steps
    assert c["engine.requests_finished|key=a"] == \
        sum(1 for st in done if st.request.key == "a")
    assert c["engine.requests_finished|key=b"] == \
        sum(1 for st in done if st.request.key == "b")


def test_scheduler_phase_spans_tile_steps(tel):
    eng = _echo()
    done = _drive(eng)
    evs = tel.tracer.trace_events()
    steps = [e for e in evs if e.get("cat") == "step"]
    phases = [e for e in evs if e.get("cat") == "phase"]
    assert len(steps) == eng.steps
    names = {e["name"] for e in phases}
    assert {"admit", "dispatch", "device_step", "gather",
            "finish"} <= names
    # every phase nests inside exactly one step span on tid 0
    for p in phases:
        assert p["tid"] == 0
        hosts = [s for s in steps
                 if s["ts"] - 1e-3 <= p["ts"] and
                 p["ts"] + p["dur"] <= s["ts"] + s["dur"] + 1e-3]
        assert len(hosts) == 1
    # request spans live on per-lane tracks
    lanes = [e for e in evs if e.get("cat") == "request"
             and e.get("ph") == "X"]
    assert len(lanes) == len(done)
    assert all(e["tid"] >= LANE_TID_BASE for e in lanes)
    # phase histograms recorded for every phase name
    hists = tel.metrics.snapshot()["histograms"]
    for name in ("admit", "dispatch", "device_step", "gather",
                 "finish"):
        assert any(k.startswith("engine.phase_s|") and
                   f"phase={name}" in k for k in hists), name


def test_traced_and_untraced_paths_agree(tel):
    traced = _drive(_echo())
    obs.disable()
    plain = _drive(_echo())
    assert len(traced) == len(plain)
    for a, b in zip(sorted(traced, key=lambda s: s.request.uid),
                    sorted(plain, key=lambda s: s.request.uid)):
        assert np.array_equal(a.result, b.result)
        assert a.pos == b.pos


def test_t_submit_stamped_before_admission_check(tel):
    eng = _echo()
    # fill key b's admission queue (queue_limit 2) -> 3rd submit rejected
    for uid in range(2):
        assert eng.submit(ItemRequest(uid=uid, items=np.ones((1, 5)),
                                      key="b"))
    rej = ItemRequest(uid=99, items=np.ones((1, 5)), key="b")
    assert not eng.submit(rej)
    assert rej.t_submit > 0.0            # stamped despite rejection
    assert eng.rejected == 1 and eng.rejected_by_key["b"] == 1
    c = tel.metrics.snapshot()["counters"]
    assert c["engine.rejected|key=b"] == 1
    assert "engine.rejected|key=a" not in c


def test_rejects_not_counted_when_disabled():
    eng = _echo()
    for uid in range(3):
        eng.submit(ItemRequest(uid=uid, items=np.ones((1, 5)), key="b"))
    assert eng.rejected == 1             # scheduler accounting intact
    assert obs.current().metrics.snapshot()["counters"] == {}


# ------------------------------------------------------------------- #
# reservoir-backed RouterStats
# ------------------------------------------------------------------- #
def test_stats_from_reservoirs_identical_to_raw_lists():
    eng = _echo()
    _drive(eng, n_a=6, n_b=2)
    assert len(eng.finished) < eng._lat_all.cap   # exact regime
    kw = dict(items=eng.items_emitted, steps=eng.steps, wall_s=1.0,
              lanes=3, rejected=eng.rejected)
    res = stats_from_states(eng.finished, lat_res=eng._lat_all,
                            wait_res=eng._wait_all, **kw)
    raw = stats_from_states(eng.finished, **kw)
    assert res == raw                    # field-for-field identical


def test_latency_reservoir_bounds_memory():
    eng = _echo(latency_reservoir=4)
    _drive(eng, n_a=8, n_b=0)
    assert len(eng.finished) == 8
    assert eng._lat_all.count == 8 and eng._lat_all.values.size == 4
    s = stats_from_states(
        eng.finished, lat_res=eng._lat_all, wait_res=eng._wait_all,
        items=eng.items_emitted, steps=eng.steps, wall_s=1.0,
        lanes=3, rejected=0)
    assert s.requests == 8               # counts stay exact
    lat = np.asarray([st.latency_s for st in eng.finished])
    assert s.latency_s_mean == pytest.approx(lat.mean())


# ------------------------------------------------------------------- #
# seeded subprocess serve: counters are deterministic run-to-run
# ------------------------------------------------------------------- #
_DETERMINISM_SCRIPT = """
import json
import numpy as np
import jax
from repro import obs
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.deploy import AppSpec, DeploymentSpec, deploy

obs.configure()
spec = MLPSpec((24, 16, 4), activation="threshold",
               out_activation="linear")
d = deploy(DeploymentSpec(apps=(
    AppSpec("app", spec,
            params=mlp_init(jax.random.PRNGKey(0), spec),
            lanes_per_chip=2),)))
rng = np.random.default_rng(7)
for i in range(5):
    d.submit("app", rng.uniform(0, 1, (2 + i % 3, 24))
             .astype(np.float32))
d.run_until_drained()
snap = d.metrics()
d.close()
print(json.dumps({"counters": snap["counters"]}))
"""


def test_subprocess_serve_counters_deterministic(sim_subprocess):
    first = sim_subprocess(_DETERMINISM_SCRIPT, n_devices=2)
    second = sim_subprocess(_DETERMINISM_SCRIPT, n_devices=2)
    assert first["counters"] == second["counters"]
    assert first["counters"]["engine.items"] == \
        sum(2 + i % 3 for i in range(5))
    assert first["counters"]["engine.requests_finished|key=app"] == 5


# ------------------------------------------------------------------- #
# the span primitive: tracer event + profiler annotation
# ------------------------------------------------------------------- #
class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``, logging every
    enter and exit by name."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        return _Ann()


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    return fake


def test_span_disabled_is_shared_null_context(monkeypatch):
    import jax.profiler

    def refuse(name):
        raise AssertionError(f"TraceAnnotation({name!r}) while disabled")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    obs.disable()
    a = obs.span("fleet.h2d", cat="fleet", rows=3)
    b = obs.span("fleet.d2h", cat="fleet")
    assert a is b
    with a as inner:
        with b:
            pass
    assert inner is a
    assert obs.current().tracer.trace_events() == []
    assert obs.current().metrics.snapshot() == \
        MetricsRegistry(enabled=False).snapshot()


def test_span_enabled_records_one_event_and_one_annotation(tel,
                                                          annotations):
    with obs.span("fleet.h2d", cat="fleet", rows=3) as sp:
        sp.args["extra"] = 1
    ev, = tel.tracer.trace_events()
    assert ev["name"] == "fleet.h2d" and ev["ph"] == "X"
    assert ev["cat"] == "fleet" and ev["tid"] == 0 and ev["dur"] >= 0
    assert ev["args"] == {"rows": 3, "extra": 1}
    assert annotations.log == [("enter", "fleet.h2d"),
                               ("exit", "fleet.h2d")]


def test_step_phases_open_profiler_annotations(tel, annotations):
    eng = _echo()
    _drive(eng)
    opened = {name for kind, name in annotations.log if kind == "enter"}
    assert {"admit", "dispatch", "device_step", "gather",
            "finish"} <= opened
    n_phases = sum(1 for e in tel.tracer.trace_events()
                   if e.get("cat") == "phase")
    assert sum(1 for kind, _ in annotations.log
               if kind == "enter") == n_phases


# ------------------------------------------------------------------- #
# the fleet member call, split into child spans
# ------------------------------------------------------------------- #
MEMBER_CHILDREN = ("fleet.stage", "fleet.h2d", "fleet.program",
                   "fleet.d2h")


@pytest.fixture(scope="module")
def member():
    import jax

    from repro.chip import compile_chip
    from repro.core.crossbar_layer import MLPSpec, mlp_init
    from repro.fleet import shard_chip

    spec = MLPSpec((64, 32, 10), activation="threshold",
                   out_activation="linear")
    chip = compile_chip(spec, params=mlp_init(jax.random.PRNGKey(0), spec))
    return shard_chip(chip, 1)


def _items(rows, seed=3):
    return np.random.default_rng(seed).uniform(
        -1, 1, (rows, 64)).astype(np.float32)


@pytest.mark.parametrize("verb, parent, children", [
    ("stream_host", "fleet.stream_host", MEMBER_CHILDREN),
    ("stream_local", "fleet.stream_local", MEMBER_CHILDREN),
    ("stream", "fleet.stream", MEMBER_CHILDREN[:3])])
def test_member_child_spans_tile_the_call(member, tel, verb, parent,
                                          children):
    call = getattr(member, verb)
    x = _items(8192)
    call(x)                                   # compiled before timing
    n0 = len(tel.tracer.trace_events())
    for _ in range(4):
        call(x)
    evs = [e for e in tel.tracer.trace_events()[n0:] if e["ph"] == "X"]
    parents = [e for e in evs if e["name"] == parent]
    assert len(parents) == 4
    assert all(e["cat"] == "fleet" and e["args"]["rows"] == 8192
               for e in parents)
    assert not any(e["name"] in MEMBER_CHILDREN and e["name"] not in
                   children for e in evs)
    for name in children:
        kids = [e for e in evs if e["name"] == name]
        assert len(kids) == 4 and all(e["cat"] == "fleet" for e in kids)
        # each child nests inside one call of the parent
        for k in kids:
            assert any(p["ts"] <= k["ts"] and
                       k["ts"] + k["dur"] <= p["ts"] + p["dur"] + 1e-3
                       for p in parents)
    total = sum(e["dur"] for e in parents)
    kids = sum(e["dur"] for e in evs if e["name"] in children)
    assert 0.90 <= kids / total <= 1.0 + 1e-9, kids / total
    assert not any(e["cat"] == "phase" for e in evs)


def test_member_byte_counters_are_exact(member, tel):
    rows = 37
    pad = -rows % member.n_chips
    member.stream_host(_items(rows))
    member.stream_host(_items(rows, seed=4))
    c = tel.metrics.snapshot()["counters"]
    padded = rows + pad
    assert c["fleet.h2d_bytes"] == 2 * padded * member.d_in * 4
    assert c["fleet.d2h_bytes"] == 2 * padded * member.d_out * 4
    assert c["fleet.pad_rows"] == 2 * pad


def test_member_traced_and_untraced_outputs_bit_identical(member):
    x = _items(300, seed=5)
    obs.disable()
    plain_host = member.stream_host(x)
    plain_dev = np.asarray(member.stream(x))
    tel = obs.configure()
    try:
        traced_host = member.stream_host(x)
        traced_dev = np.asarray(member.stream(x))
        names = [e["name"] for e in tel.tracer.trace_events()]
    finally:
        obs.disable()
    assert np.array_equal(plain_host, traced_host)
    assert np.array_equal(plain_dev, traced_dev)
    assert np.array_equal(plain_host, plain_dev)
    # stream keeps its output on the device, so the traced pair opens
    # one fleet.stream_host, one fleet.stream, one read-back, no upload
    assert names.count("fleet.stream_host") == 1
    assert names.count("fleet.stream") == 1
    assert names.count("fleet.d2h") == 1
    assert names.count("fleet.result_to_device") == 0


def test_member_stream_result_stays_on_the_device(member, tel):
    import jax

    x = _items(37, seed=6)
    host = member.stream_host(x)
    c0 = dict(tel.metrics.snapshot()["counters"])
    outs = [member.stream(x), member(x)]
    c = tel.metrics.snapshot()["counters"]
    for y in outs:
        assert isinstance(y, jax.Array) and y.dtype == np.float32
        assert np.array_equal(np.asarray(y), host)
    assert c["fleet.d2h_bytes"] == c0["fleet.d2h_bytes"]
    assert c["fleet.h2d_bytes"] - c0["fleet.h2d_bytes"] == \
        2 * 37 * member.d_in * 4
    names = [e["name"] for e in tel.tracer.trace_events()]
    assert names.count("fleet.stream") == 2
    assert names.count("fleet.d2h") == 1
    assert "fleet.result_to_device" not in names


@pytest.mark.parametrize("shape", [(64,), (3, 5, 64)])
def test_member_stream_keeps_the_lead_shape_and_dtype(member, shape):
    import jax.numpy as jnp

    x = _items(int(np.prod(shape[:-1])), seed=7).reshape(shape)
    host = member.stream_host(x)
    y = member.stream(x)
    assert y.shape == host.shape == (*shape[:-1], member.d_out)
    assert np.array_equal(np.asarray(y), host)
    # the caller's dtype comes back, as the host path's upload gave it
    y16 = member.stream(jnp.asarray(x, jnp.bfloat16))
    assert y16.dtype == jnp.bfloat16


def test_chip_stream_span_through_the_primitive(member, tel, annotations):
    import jax.numpy as jnp

    member.chip.stream(jnp.asarray(_items(16)))
    ev, = [e for e in tel.tracer.trace_events()
           if e["name"] == "chip.stream"]
    assert ev["cat"] == "chip" and ev["args"]["rows"] == 16
    assert ev["args"]["compile_delta"] == 0
    assert ("enter", "chip.stream") in annotations.log
    snap = tel.metrics.snapshot()
    assert "chip.stream_s" not in snap["histograms"]
    assert "chip.items_streamed" not in snap["counters"]
    assert "chip.compile_count" not in snap["gauges"]
