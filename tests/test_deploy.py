"""repro.deploy: declarative multi-app deployment over one fabric.

Covers the PR-5 acceptance surface:

  * single-app equivalence — ``compile_chip`` → ``shard_chip`` vs
    ``deploy()``'s single-app path at rel 0.0, memristor AND digital,
    for the direct stream, and the routed serving loop against the
    member's own ``ShardedChip.stream_host`` of the same items;
  * the system-name alias matrix through the one normalize helper;
  * multi-app co-residency — per-app lanes/admission isolation on one
    shared mesh, routed outputs matching each tenant's own programmed
    plan, per-app stats summing EXACTLY to the fleet roll-up;
  * the payload-keyed scheduler's contract at the engine level
    (per-key FIFO, no head-of-line blocking across keys, per-key
    backpressure);
  * ``reprogram`` — a live weight swap with zero compile passes
    (``compile_count`` instrumentation + mapping identity) that lands
    bit-exactly on a freshly compiled reference;
  * report composition (pure, meshless) and a 2-simulated-device
    subprocess end-to-end.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.chip import compile_app, compile_chip, compile_count
from repro.chip import compile as chip_compile
from repro.configs.paper_apps import APPS
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.core.systems import (CANONICAL_SYSTEMS, normalize_system,
                                system_mode)
from repro.deploy import (AppSpec, DeploymentSpec, deploy,
                          deployment_report)
from repro.fleet import shard_chip
from repro.serving.engine import (ItemRequest, KeyedItemStreamScheduler,
                                  StreamSpec)

DIMS_A = (64, 48, 10)
DIMS_B = (32, 16, 4)


@pytest.fixture(scope="module")
def spec_a():
    return MLPSpec(DIMS_A, activation="threshold",
                   out_activation="linear")


@pytest.fixture(scope="module")
def spec_b():
    return MLPSpec(DIMS_B, activation="threshold",
                   out_activation="linear")


@pytest.fixture(scope="module")
def params_a(spec_a):
    return mlp_init(jax.random.PRNGKey(0), spec_a)


@pytest.fixture(scope="module")
def params_b(spec_b):
    return mlp_init(jax.random.PRNGKey(7), spec_b)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


# ------------------------------------------------------------------- #
# system-name normalization (satellite)
# ------------------------------------------------------------------- #
def test_normalize_system_alias_matrix():
    matrix = {
        "memristor": "memristor", "1t1m": "memristor",
        "crossbar": "memristor", "digital": "digital", "sram": "digital",
        # case/whitespace-insensitive
        "1T1M": "memristor", " SRAM ": "digital", "Memristor": "memristor",
    }
    for alias, canon in matrix.items():
        assert normalize_system(alias) == canon
        assert canon in CANONICAL_SYSTEMS
    assert system_mode("1t1m") == "crossbar"
    assert system_mode("sram") == "digital"
    with pytest.raises(ValueError, match="unknown system"):
        normalize_system("risc")
    with pytest.raises(ValueError, match="unknown system"):
        normalize_system("")
    with pytest.raises(TypeError):
        normalize_system(3)


def test_compile_and_costmodel_accept_aliases(spec_a, params_a):
    from repro.core.costmodel import specialized_cost

    by_alias = {alias: compile_chip(spec_a, params=params_a,
                                    system=alias).report()
                for alias in ("memristor", "1t1m", "digital", "sram")}
    assert by_alias["memristor"] == by_alias["1t1m"]
    assert by_alias["digital"] == by_alias["sram"]
    assert by_alias["memristor"].system == "memristor"
    assert by_alias["sram"].system == "digital"
    # "1t1m" used to fall through specialized_cost's digital branch
    app = APPS["deep"]
    assert specialized_cost(app, "1t1m").cores == \
        specialized_cost(app, "memristor").cores
    assert specialized_cost(app, "sram").cores == \
        specialized_cost(app, "digital").cores
    with pytest.raises(ValueError, match="unknown system"):
        compile_chip(spec_a, params=params_a, system="analog")


def test_appspec_normalizes_system_eagerly():
    assert AppSpec("x", DIMS_A, system="1T1M").system == "memristor"
    assert AppSpec("x", DIMS_A, system="sram").system == "digital"
    with pytest.raises(ValueError, match="unknown system"):
        AppSpec("x", DIMS_A, system="tpu")


# ------------------------------------------------------------------- #
# single-app equivalence: hand wiring vs deploy()
# ------------------------------------------------------------------- #
@pytest.mark.parametrize("system", ["memristor", "digital"])
def test_single_app_deploy_matches_legacy_path(system, spec_a,
                                               params_a):
    chip = compile_chip(spec_a, params=params_a, system=system)
    fleet = shard_chip(chip)
    d = deploy(AppSpec("app", spec_a, params=params_a, system=system))

    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(3),
                                      (9, DIMS_A[0])), np.float32)
    assert _rel(d.stream("app", x), fleet.stream(x)) == 0.0

    # the routed serving loop too: a ragged burst through the
    # deployment, each served result identical to the member's own
    # host stream of the same items (the verb the router calls)
    rng = np.random.default_rng(5)
    bursts = [rng.uniform(0, 1, (2 + i, DIMS_A[0])).astype(np.float32)
              for i in range(4)]
    for items in bursts:
        assert d.submit("app", items.copy())
    deploy_done = d.run_until_drained()
    assert len(deploy_done) == len(bursts)
    member = d.router.members["app"]
    for uid, dst in enumerate(sorted(deploy_done,
                                     key=lambda s: s.request.uid)):
        want = member.stream_host(bursts[uid])
        assert _rel(dst.result, fleet.stream_host(bursts[uid])) == 0.0
        assert _rel(dst.result, want) == 0.0
    st = d.stats()
    assert st.fleet.requests == len(bursts)
    assert st.apps["app"].items == st.fleet.items == \
        sum(b.shape[0] for b in bursts)
    d.close()


@pytest.mark.parametrize("system", ["memristor", "digital"])
def test_stream_returns_device_array_identical_to_stream_host(
        system, spec_a, params_a):
    chip = compile_chip(spec_a, params=params_a, system=system)
    fleet = shard_chip(chip, 1)
    d = deploy(AppSpec("app", spec_a, params=params_a, system=system))
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(4),
                                      (64, DIMS_A[0])), np.float32)
    host = fleet.stream_host(x)
    for y in (d.stream("app", x), fleet.stream(x), fleet(x)):
        assert isinstance(y, jax.Array) and y.dtype == np.float32
        assert y.shape == host.shape
        assert np.array_equal(np.asarray(y), host)
    d.close()


def test_stream_input_may_be_overwritten_on_return(spec_a, params_a):
    # a frame buffer reused at once: 64-byte aligned float32 rows, which
    # the CPU client places without a copy, so the call has to be done
    # with them before it returns
    d = deploy(AppSpec("app", spec_a, params=params_a))
    rows, width = 4096, DIMS_A[0]
    buf = np.empty(rows * width * 4 + 64, np.uint8)
    off = -buf.ctypes.data % 64
    x = buf[off:off + rows * width * 4].view(np.float32).reshape(
        rows, width)
    x[:] = np.random.default_rng(8).uniform(-1, 1, (rows, width))
    want = d.stream("app", x.copy())
    for _ in range(3):
        y = d.stream("app", x)
        x_was = x.copy()
        x[:] = 0.0
        assert np.array_equal(np.asarray(y), np.asarray(want))
        x[:] = x_was
    d.close()


# ------------------------------------------------------------------- #
# payload-keyed scheduler (engine level)
# ------------------------------------------------------------------- #
class _EchoScheduler(KeyedItemStreamScheduler):
    """Identity payload with a per-key gain, so outputs identify both
    the item AND which stream processed it."""

    GAINS = {"a": 2.0, "b": -3.0}

    def _stream_batch_key(self, key, batch):
        return batch * self.GAINS[key]


def _echo():
    return _EchoScheduler({
        "a": StreamSpec(d_in=3, lanes=2, queue_limit=None),
        "b": StreamSpec(d_in=5, lanes=1, queue_limit=2),
    })


def test_keyed_scheduler_routes_and_accounts_per_key():
    eng = _echo()
    reqs = [ItemRequest(uid=0, items=np.ones((2, 3)), key="a"),
            ItemRequest(uid=1, items=np.ones((3, 5)), key="b"),
            ItemRequest(uid=2, items=np.full((1, 3), 4.0), key="a")]
    for r in reqs:
        assert eng.submit(r)
    done = eng.run_until_drained()
    assert len(done) == 3
    by_uid = {st.request.uid: st for st in done}
    assert np.array_equal(by_uid[0].result, np.full((2, 3), 2.0))
    assert np.array_equal(by_uid[1].result, np.full((3, 5), -3.0))
    assert np.array_equal(by_uid[2].result, np.full((1, 3), 8.0))
    assert eng.items_by_key == {"a": 3, "b": 3}
    assert eng.items_emitted == 6


def test_keyed_scheduler_no_cross_key_head_of_line_blocking():
    eng = _echo()
    # saturate key a's 2 lanes AND its queue head
    for uid in range(3):
        eng.submit(ItemRequest(uid=uid, items=np.ones((4, 3)), key="a"))
    eng.step()
    assert len(eng.active) == 2 and len(eng.queue) == 1
    # b arrives behind a's queued request — and must NOT wait for it
    eng.submit(ItemRequest(uid=10, items=np.ones((1, 5)), key="b"))
    eng.step()
    finished_b = [st for st in eng.finished if st.request.key == "b"]
    assert len(finished_b) == 1          # b ran while a was saturated
    eng.run_until_drained()
    assert len(eng.finished) == 4


def test_keyed_scheduler_per_key_backpressure_and_unknown_key():
    eng = _echo()
    # key b: 1 lane busy + queue_limit 2
    assert eng.submit(ItemRequest(uid=0, items=np.ones((9, 5)), key="b"))
    eng.step()                           # uid 0 occupies b's only lane
    assert eng.submit(ItemRequest(uid=1, items=np.ones((1, 5)), key="b"))
    assert eng.submit(ItemRequest(uid=2, items=np.ones((1, 5)), key="b"))
    # b's admission queue is now full — rejected, per-key accounted
    assert not eng.submit(ItemRequest(uid=3, items=np.ones((1, 5)),
                                      key="b"))
    assert eng.rejected == 1 and eng.rejected_by_key == {"a": 0, "b": 1}
    # a is unaffected by b's backpressure
    assert eng.submit(ItemRequest(uid=4, items=np.ones((1, 3)), key="a"))
    with pytest.raises(ValueError, match="unknown stream key"):
        eng.submit(ItemRequest(uid=5, items=np.ones((1, 3)),
                               key="nope"))
    with pytest.raises(ValueError, match="features"):
        eng.submit(ItemRequest(uid=6, items=np.ones((1, 4)), key="a"))
        eng.run_until_drained()


def test_keyed_scheduler_malformed_request_costs_only_itself():
    """A wrong-width request raising at admission must not drop the
    requests queued behind it, leak its lane, or leave phantom queue
    accounting behind."""
    eng = _echo()
    eng.submit(ItemRequest(uid=0, items=np.ones((1, 3)), key="a"))
    eng.submit(ItemRequest(uid=1, items=np.ones((1, 4)), key="a"))  # bad
    eng.submit(ItemRequest(uid=2, items=np.ones((1, 3)), key="a"))
    with pytest.raises(ValueError, match="features"):
        eng.step()
    # uid 0 was admitted before the failure; uid 2 survived behind it
    assert [r.uid for r in eng.queue] == [2]
    done = eng.run_until_drained()
    assert sorted(st.request.uid for st in done) == [0, 2]
    # the bad request's lane went back: both of a's lanes usable again
    eng.submit(ItemRequest(uid=3, items=np.ones((1, 3)), key="a"))
    eng.submit(ItemRequest(uid=4, items=np.ones((1, 3)), key="a"))
    eng.step()
    assert len(eng.active) == 0 and len(eng.finished) == 4
    # key b's bounded queue still admits exactly queue_limit waiters
    # (no phantom occupancy from a's failure)
    assert eng.submit(ItemRequest(uid=5, items=np.ones((1, 5)), key="b"))
    assert eng.submit(ItemRequest(uid=6, items=np.ones((1, 5)), key="b"))
    eng.run_until_drained()
    assert len(eng.finished) == 6


# ------------------------------------------------------------------- #
# multi-app co-residency
# ------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def duo(spec_a, spec_b, params_a, params_b):
    d = deploy(DeploymentSpec(apps=(
        AppSpec("alpha", spec_a, params=params_a, system="1t1m",
                lanes_per_chip=2),
        AppSpec("beta", spec_b, params=params_b, system="sram",
                lanes_per_chip=1),
    )))
    yield d
    d.close()


def test_multiapp_streams_match_per_app_chips(duo, spec_a, spec_b,
                                              params_a, params_b):
    xa = np.asarray(jax.random.uniform(jax.random.PRNGKey(11),
                                       (5, DIMS_A[0])), np.float32)
    xb = np.asarray(jax.random.uniform(jax.random.PRNGKey(12),
                                       (5, DIMS_B[0])), np.float32)
    ref_a = compile_chip(spec_a, params=params_a,
                         system="memristor").stream(xa)
    ref_b = compile_chip(spec_b, params=params_b,
                         system="digital").stream(xb)
    assert _rel(duo.stream("alpha", xa), ref_a) == 0.0
    assert _rel(duo.stream("beta", xb), ref_b) == 0.0


def test_multiapp_roundtrip_and_exact_stats_rollup(duo):
    rng = np.random.default_rng(21)
    sub_a = [rng.uniform(0, 1, (2 + i, DIMS_A[0])).astype(np.float32)
             for i in range(3)]
    sub_b = [rng.uniform(0, 1, (1 + i, DIMS_B[0])).astype(np.float32)
             for i in range(4)]
    for items in sub_a:
        assert duo.submit("alpha", items)
    for items in sub_b:
        assert duo.submit("beta", items)
    done = list(duo.run_until_drained())
    assert len(done) == len(sub_a) + len(sub_b)
    for st in done:
        chip = duo.chip(st.request.key)
        assert _rel(st.result, chip.stream(st.request.items)) == 0.0

    stats = duo.stats()
    assert set(stats.apps) == {"alpha", "beta"}
    for field in ("requests", "items", "rejected", "lanes"):
        assert sum(getattr(s, field) for s in stats.apps.values()) == \
            getattr(stats.fleet, field)
    assert stats.apps["alpha"].items == sum(a.shape[0] for a in sub_a)
    assert stats.apps["beta"].items == sum(b.shape[0] for b in sub_b)
    assert stats.fleet.steps == stats.apps["alpha"].steps
    # report folds the served roll-up in
    rep = duo.report()
    assert rep.served is not None
    assert rep.cores == sum(f.cores for f in rep.apps.values())


def test_per_app_admission_budgets(spec_a, spec_b, params_a, params_b):
    d = deploy(DeploymentSpec(apps=(
        AppSpec("alpha", spec_a, params=params_a, lanes_per_chip=1),
        AppSpec("beta", spec_b, params=params_b, system="digital",
                lanes_per_chip=1, queue_limit=1),
    )))
    # beta: lane busy + queue full → third submit rejected
    assert d.submit("beta", np.ones((6, DIMS_B[0]), np.float32))
    d.step()
    assert d.submit("beta", np.ones((1, DIMS_B[0]), np.float32))
    assert not d.submit("beta", np.ones((1, DIMS_B[0]), np.float32))
    # alpha (no limit) is not affected by beta's backpressure
    assert d.submit("alpha", np.ones((1, DIMS_A[0]), np.float32))
    d.run_until_drained()
    stats = d.stats()
    assert stats.apps["beta"].rejected == 1 == stats.fleet.rejected
    assert stats.apps["alpha"].rejected == 0
    d.close()


def test_spec_validation_and_unknown_apps(spec_a, params_a):
    with pytest.raises(ValueError, match="duplicate app names"):
        DeploymentSpec(apps=(AppSpec("x", spec_a),
                             AppSpec("x", spec_a)))
    with pytest.raises(ValueError, match="at least one"):
        DeploymentSpec(apps=())
    with pytest.raises(ValueError, match="lanes_per_chip"):
        AppSpec("x", spec_a, lanes_per_chip=0)
    with pytest.raises(ValueError, match="unknown paper app"):
        deploy(AppSpec("x", "sobel"))
    d = deploy(AppSpec("app", spec_a, params=params_a))
    with pytest.raises(ValueError, match="unknown app"):
        d.stream("nope", np.ones((1, DIMS_A[0]), np.float32))
    d.close()
    with pytest.raises(RuntimeError, match="closed"):
        d.stats()


def test_analytic_flag_skips_programming(spec_a):
    """analytic=True tenants compile report-only: no weight synthesis,
    no tile programming — the cheap sizing path (quickstart part 1)."""
    from repro.deploy import single_app

    # reachable through the shorthand too
    spec1 = single_app("deep", system="1t1m", analytic=True)
    assert spec1.apps[0].analytic
    d = deploy(AppSpec("deep", "deep", system="1t1m", analytic=True))
    assert d.chip("deep").plan is None
    assert d.router is None
    assert d.chip("deep").report().cores == \
        compile_app(APPS["deep"], "1t1m").report().cores
    with pytest.raises(ValueError, match="analytic-only"):
        d.stream("deep", np.ones((1, 784), np.float32))
    d.close()
    with pytest.raises(ValueError, match="report-only"):
        AppSpec("x", spec_a, params=[], analytic=True)


def test_paper_app_tenants_stream_and_report():
    d = deploy(DeploymentSpec(apps=(
        AppSpec("deep", "deep", system="1t1m", lanes_per_chip=1),
        AppSpec("edge", "edge", system="1t1m"),   # multi-net: analytic
    )))
    # deep: streamable with deterministic weights, at the paper's rate
    assert d.chip("deep").items_per_second == \
        APPS["deep"].items_per_second
    x = np.ones((2, 784), np.float32)
    assert d.stream("deep", x).shape == (2, 10)
    # edge: report-only tenant
    with pytest.raises(ValueError, match="analytic-only"):
        d.stream("edge", np.ones((1, 9), np.float32))
    rep = d.report()
    assert set(rep.apps) == {"deep", "edge"}
    assert rep.apps["edge"].chip.cores == \
        compile_app(APPS["edge"], "1t1m").report().cores
    # a bare source binds to the single streamable app
    class _Pipe:
        def batch(self, step):
            return np.full((2, 784), 0.5, np.float32)
    from repro.fleet import StreamSource
    done = d.serve(StreamSource(_Pipe(), n_requests=3, capacity=2))
    assert len(done) == 3
    d.close()


# ------------------------------------------------------------------- #
# reprogram: the live weight swap
# ------------------------------------------------------------------- #
def test_reprogram_swaps_weights_without_recompiling(spec_a, params_a):
    d = deploy(AppSpec("app", spec_a, params=params_a))
    mapping_before = d.chip("app").mapping
    route_before = d.chip("app").route
    params2 = mlp_init(jax.random.PRNGKey(99), spec_a)
    n = compile_count()
    d.reprogram("app", params2)
    assert compile_count() == n          # ZERO compile passes
    # fabric identity: the mapping/route objects are literally reused
    assert d.chip("app").mapping is mapping_before
    assert d.chip("app").route is route_before

    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(33),
                                      (7, DIMS_A[0])), np.float32)
    ref = compile_chip(spec_a, params=params2, system="memristor")
    assert _rel(d.stream("app", x), ref.stream(x)) == 0.0
    # and the swap is visible through the ROUTER path too
    assert d.submit("app", x)
    st = d.run_until_drained()[-1]
    assert _rel(st.result, ref.stream(x)) == 0.0
    d.close()


def test_reprogram_preserves_compile_time_quantization(spec_b,
                                                       params_b):
    """A bare reprogram must re-encode with the knobs the chip was
    COMPILED with (weight_bits etc. ride on CompiledChip.program_kw),
    not the library defaults — otherwise a 'weights-only' swap on a
    4-bit chip silently becomes an 8-bit chip."""
    from repro.chip import reprogram_chip

    params2 = mlp_init(jax.random.PRNGKey(3), spec_b)
    chip4 = compile_chip(spec_b, params=params_b, system="digital",
                         weight_bits=4)
    swapped = reprogram_chip(chip4, params2)      # no kwargs
    ref4 = compile_chip(spec_b, params=params2, system="digital",
                        weight_bits=4)
    ref8 = compile_chip(spec_b, params=params2, system="digital")
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(4),
                                      (6, DIMS_B[0])), np.float32)
    assert _rel(swapped.stream(x), ref4.stream(x)) == 0.0
    assert _rel(ref4.stream(x), ref8.stream(x)) > 0.0  # bits matter


def test_reprogram_preserves_heterogeneous_activations(params_a):
    """A chip compiled from a hand-built ProgrammedMLP with per-layer
    activations must keep that schedule through reprogram (MLPSpec can
    only express hidden/out, so the plan is the source of truth)."""
    import dataclasses as dc

    from repro.chip import reprogram_chip
    from repro.core.crossbar_layer import program_mlp

    from repro.core.device import DEFAULT_DEVICE

    spec = MLPSpec((64, 48, 10))
    prog = program_mlp(params_a, spec, mode="crossbar")
    prog = dc.replace(prog, activations=("sigmoid", "relu"))
    chip = compile_chip(prog, system="memristor")
    assert tuple(l.activation for l in chip.plan) == ("sigmoid", "relu")
    params2 = mlp_init(jax.random.PRNGKey(5), spec)
    # a chip compiled from pre-programmed state does not know how its
    # tiles were encoded: a bare reprogram must refuse, not guess
    with pytest.raises(ValueError, match="pre-programmed"):
        reprogram_chip(chip, params2)
    chip2 = reprogram_chip(chip, params2, weight_bits=8,
                           device=DEFAULT_DEVICE, r_seg=0.0)
    assert tuple(l.activation for l in chip2.plan) == \
        ("sigmoid", "relu")


def test_reprogram_rejects_wrong_topology_and_analytic(spec_a,
                                                       params_a):
    from repro.chip import reprogram_chip

    d = deploy(AppSpec("app", spec_a, params=params_a))
    bad = mlp_init(jax.random.PRNGKey(1),
                   MLPSpec((64, 32, 10)))       # different hidden width
    with pytest.raises(ValueError, match="do not match"):
        d.reprogram("app", bad)
    deeper = mlp_init(jax.random.PRNGKey(1),
                      MLPSpec((64, 48, 10, 10)))   # extra layer
    with pytest.raises(ValueError, match="do not match"):
        d.reprogram("app", deeper)
    d.close()
    analytic = compile_chip(spec_a, system="memristor")
    with pytest.raises(ValueError, match="analytic-only"):
        reprogram_chip(analytic, params_a)


# ------------------------------------------------------------------- #
# report composition (pure — no devices needed)
# ------------------------------------------------------------------- #
def test_deployment_report_composes_linearly():
    chips = {"deep": compile_app(APPS["deep"], "1t1m"),
             "ocr": compile_app(APPS["ocr"], "digital")}
    rep = deployment_report(chips, 3)
    assert rep.n_chips == 3 and set(rep.apps) == {"deep", "ocr"}
    for name, chip in chips.items():
        cr = chip.report()
        assert rep.apps[name].cores == cr.cores * 3
        assert rep.apps[name].area_mm2 == pytest.approx(
            cr.area_mm2 * 3, rel=1e-12)
    assert rep.cores == sum(f.cores for f in rep.apps.values())
    assert rep.area_mm2 == pytest.approx(
        sum(f.area_mm2 for f in rep.apps.values()), rel=1e-12)
    assert rep.power_mw == pytest.approx(
        sum(f.power_mw for f in rep.apps.values()), rel=1e-12)
    assert rep.capacity_items_per_second == pytest.approx(
        sum(f.capacity_items_per_second for f in rep.apps.values()),
        rel=1e-12)
    assert rep.served is None


# ------------------------------------------------------------------- #
# queue_limit boundary semantics (satellite)
# ------------------------------------------------------------------- #
def test_queue_limit_zero_is_an_explicit_error(spec_a):
    """0 used to be ambiguous between "unbounded" (falsy) and "reject
    everything" (a zero-capacity queue never admits): now a loud
    ValueError at spec build, on both the app and deployment level."""
    for bad in (0, -3, True, 2.0):
        with pytest.raises(ValueError, match="queue_limit"):
            AppSpec("x", spec_a, queue_limit=bad)
        with pytest.raises(ValueError, match="queue_limit"):
            DeploymentSpec(apps=(AppSpec("x", spec_a),),
                           queue_limit=bad)
    # boundary: 1 is the smallest bounded queue; None means unbounded
    assert AppSpec("x", spec_a, queue_limit=1).queue_limit == 1
    assert AppSpec("x", spec_a).queue_limit is None
    assert DeploymentSpec(apps=(AppSpec("x", spec_a),),
                          queue_limit=1).queue_limit == 1
    assert DeploymentSpec(apps=(AppSpec("x", spec_a),)).queue_limit \
        is None


def test_queue_limit_none_admits_unboundedly(spec_a, params_a):
    d = deploy(AppSpec("a", spec_a, params=params_a,
                       lanes_per_chip=1))
    admitted = [d.submit("a", np.ones((1, DIMS_A[0]), np.float32))
                for _ in range(12)]
    assert all(admitted)
    d.run_until_drained()
    assert d.stats().fleet.rejected == 0
    d.close()


# ------------------------------------------------------------------- #
# rate validation fires exactly ONCE, with both capacity scopes
# (satellite)
# ------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def deep_mlp():
    """The deep app's dims: compute capacity exceeds the routed TDM
    limit, so a rate that drives every replica at compute capacity is
    un-routable — the canonical infeasible-SLO construction (same as
    test_chip's)."""
    mspec = MLPSpec((784, 200, 100, 10), activation="threshold",
                    out_activation="linear")
    return mspec, mlp_init(jax.random.PRNGKey(25), mspec)


def test_deploy_rate_warning_fires_exactly_once(deep_mlp):
    """deploy() used to warn twice for one infeasible SLO (compile
    then shard); now exactly one ChipRateWarning, carrying BOTH the
    per-chip and fleet-wide capacity numbers."""
    mspec, params = deep_mlp
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d = deploy(AppSpec("a", mspec, params=params,
                           items_per_second=1e12))
    rate = [x for x in w
            if issubclass(x.category, chip_compile.ChipRateWarning)]
    assert len(rate) == 1, [str(x.message) for x in rate]
    msg = str(rate[0].message)
    assert "items/s per chip" in msg and "items/s fleet-wide" in msg
    d.close()


def test_deploy_rate_warning_once_for_analytic_tenants():
    """The analytic-only path validates at the same fleet scope,
    also exactly once."""
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d = deploy(AppSpec("deep", "deep", analytic=True,
                           items_per_second=1e12))
    rate = [x for x in w
            if issubclass(x.category, chip_compile.ChipRateWarning)]
    assert len(rate) == 1, [str(x.message) for x in rate]
    assert "items/s fleet-wide" in str(rate[0].message)
    d.close()


def test_deploy_strict_rate_raises_exactly_once(deep_mlp):
    mspec, params = deep_mlp
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with pytest.raises(ValueError, match="items/s fleet-wide"):
            deploy(DeploymentSpec(
                apps=(AppSpec("a", mspec, params=params,
                              items_per_second=1e12),),
                strict_rate=True))
    assert not [x for x in w
                if issubclass(x.category,
                              chip_compile.ChipRateWarning)]


def test_legacy_compile_then_shard_validates_once(deep_mlp):
    """compile_chip at a rate already vouches for it; shard_chip at
    the SAME rate must not warn again (the fleet check is vacuous
    when the chip-level one passed or already diagnosed). A DIFFERENT
    fleet rate still re-validates."""
    mspec, params = deep_mlp
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        chip = compile_chip(mspec, params=params,
                            items_per_second=1e12)
        shard_chip(chip, items_per_second=1e12)
    rate = [x for x in w
            if issubclass(x.category, chip_compile.ChipRateWarning)]
    assert len(rate) == 1, [str(x.message) for x in rate]
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        shard_chip(chip, items_per_second=2e12)
    rate2 = [x for x in w2
             if issubclass(x.category, chip_compile.ChipRateWarning)]
    assert len(rate2) == 1
    assert "items/s per chip" in str(rate2[0].message)


# ------------------------------------------------------------------- #
# heterogeneous chip_systems specs (tentpole surface)
# ------------------------------------------------------------------- #
def test_chip_systems_spec_validation(spec_a, spec_b):
    with pytest.raises(ValueError, match="n_chips or mesh"):
        DeploymentSpec(apps=(AppSpec("a", spec_a),), n_chips=2,
                       chip_systems=("memristor", "digital"))
    with pytest.raises(ValueError, match="at least one chip"):
        DeploymentSpec(apps=(AppSpec("a", spec_a),), chip_systems=())
    with pytest.raises(ValueError, match="no chip in chip_systems"):
        DeploymentSpec(apps=(AppSpec("a", spec_a, system="digital"),),
                       chip_systems=("memristor",))
    # aliases normalize, and app->submesh coverage is checked
    s = DeploymentSpec(apps=(AppSpec("a", spec_a),
                             AppSpec("b", spec_b, system="sram")),
                       chip_systems=("1t1m", "sram"))
    assert s.chip_systems == ("memristor", "digital")


def test_appspec_geom_validation(spec_a):
    with pytest.raises(ValueError, match="geom"):
        AppSpec("x", spec_a, geom=(128,))
    with pytest.raises(ValueError, match="geom"):
        AppSpec("x", spec_a, geom=(128, 0))
    assert AppSpec("x", spec_a, geom=[128, 64]).geom == (128, 64)


def test_heterogeneous_fleet_refuses_resize_and_singleproc_mesh(
        spec_a, spec_b, params_a, params_b):
    """On 1 visible device a 2-system fleet cannot build (one chip per
    declared system); with enough devices it refuses resize() — both
    loud errors, not silent truncation. The full mixed-mesh serving
    path runs in the 2-device subprocess test below."""
    spec = DeploymentSpec(apps=(
        AppSpec("a", spec_a, params=params_a),
        AppSpec("b", spec_b, params=params_b, system="digital"),
    ), chip_systems=("memristor", "digital"))
    if len(jax.devices()) < 2:
        with pytest.raises(ValueError, match="chips requested"):
            deploy(spec)
        return
    d = deploy(spec)
    with pytest.raises(ValueError, match="chip_systems"):
        d.resize(1)
    d.close()


# ------------------------------------------------------------------- #
# 2 simulated devices, end to end (subprocess)
# ------------------------------------------------------------------- #
_TWO_DEVICE_SCRIPT = """
import json
import jax
import numpy as np
from repro.chip import compile_chip
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.deploy import AppSpec, DeploymentSpec, deploy
from repro.fleet import shard_chip

spec_a = MLPSpec((64, 48, 10), activation="threshold",
                 out_activation="linear")
spec_b = MLPSpec((32, 16, 4), activation="threshold",
                 out_activation="linear")
pa = mlp_init(jax.random.PRNGKey(0), spec_a)
pb = mlp_init(jax.random.PRNGKey(7), spec_b)
d = deploy(DeploymentSpec(apps=(
    AppSpec("alpha", spec_a, params=pa, lanes_per_chip=2),
    AppSpec("beta", spec_b, params=pb, system="digital"),
)))
legacy = shard_chip(compile_chip(spec_a, params=pa))
x = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (11, 64)),
               np.float32)
rel = float(np.max(np.abs(np.asarray(d.stream("alpha", x)) -
                          np.asarray(legacy.stream(x)))))
rng = np.random.default_rng(3)
for i in range(4):
    d.submit("alpha", rng.uniform(0, 1, (3, 64)).astype(np.float32))
    d.submit("beta", rng.uniform(0, 1, (2, 32)).astype(np.float32))
d.run_until_drained()
s = d.stats()
print(json.dumps({
    "devices": len(jax.devices()),
    "n_chips": d.n_chips,
    "rel": rel,
    "exact": (sum(a.requests for a in s.apps.values()) ==
              s.fleet.requests == 8 and
              sum(a.items for a in s.apps.values()) ==
              s.fleet.items == 20 and
              sum(a.lanes for a in s.apps.values()) == s.fleet.lanes),
}))
"""


def test_two_device_deployment_subprocess(sim_subprocess):
    res = sim_subprocess(_TWO_DEVICE_SCRIPT, n_devices=2)
    assert res["devices"] == 2 and res["n_chips"] == 2
    assert res["rel"] == 0.0
    assert res["exact"]


_RAGGED_STREAM_SCRIPT = """
import json
import jax
import numpy as np
from repro import obs
from repro.chip import compile_chip
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.fleet import shard_chip

spec = MLPSpec((64, 48, 10), activation="threshold",
               out_activation="linear")
fleet = shard_chip(compile_chip(spec, params=mlp_init(
    jax.random.PRNGKey(0), spec)))
rng = np.random.default_rng(2)
odd, even = (rng.uniform(-1, 1, (n, 64)).astype(np.float32)
             for n in (11, 12))
tel = obs.configure()
res = {"n_chips": fleet.n_chips}
for name, x in (("odd", odd), ("even", even)):
    n0 = len(tel.tracer.trace_events())
    y = fleet.stream(x)
    names = [e["name"] for e in tel.tracer.trace_events()[n0:]]
    res[name] = {
        "match": bool(np.array_equal(np.asarray(y),
                                     fleet.stream_host(x))),
        "shape": list(y.shape),
        "devices": len(y.sharding.device_set),
        "replicated": bool(y.sharding.is_fully_replicated),
        "spans": {k: names.count(k) for k in (
            "fleet.stream", "fleet.stream_host", "fleet.d2h",
            "fleet.program")},
    }
print(json.dumps(res))
"""


def test_ragged_stream_cuts_its_pad_rows_on_the_device_subprocess(
        sim_subprocess):
    res = sim_subprocess(_RAGGED_STREAM_SCRIPT, n_devices=2)
    assert res["n_chips"] == 2
    odd, even = res["odd"], res["even"]
    assert odd["match"] and even["match"]
    assert odd["shape"] == [11, 10] and even["shape"] == [12, 10]
    # both stay on the member's two chips with no read-back: 11 rows
    # leave a pad row, cut on the device (replicated: the count is odd);
    # 12 rows divide, and the output stays sharded
    for r in (odd, even):
        assert r["spans"] == {"fleet.stream": 1, "fleet.stream_host": 0,
                              "fleet.d2h": 0, "fleet.program": 1}
        assert r["devices"] == 2
    assert odd["replicated"] and not even["replicated"]


# ------------------------------------------------------------------- #
# heterogeneous fleet, end to end (subprocess, tentpole)
# ------------------------------------------------------------------- #
_HETERO_SCRIPT = """
import json
import jax
import numpy as np
from repro.chip import compile_chip
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.deploy import AppSpec, DeploymentSpec, deploy
from repro.fleet import shard_chip

spec_a = MLPSpec((64, 48, 10), activation="threshold",
                 out_activation="linear")
spec_b = MLPSpec((32, 16, 4), activation="threshold",
                 out_activation="linear")
pa = mlp_init(jax.random.PRNGKey(0), spec_a)
pb = mlp_init(jax.random.PRNGKey(7), spec_b)
d = deploy(DeploymentSpec(apps=(
    AppSpec("mem", spec_a, params=pa, lanes_per_chip=2),
    AppSpec("dig", spec_b, params=pb, system="digital"),
), chip_systems=("memristor", "digital")))

# each app streams on ITS system's single-chip submesh, bit-equal to
# the legacy single-system path on one chip
rels = {}
for name, mspec, p, din in (("mem", spec_a, pa, 64),
                            ("dig", spec_b, pb, 32)):
    system = "memristor" if name == "mem" else "digital"
    legacy = shard_chip(compile_chip(mspec, params=p, system=system),
                        n_chips=1)
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                      (7, din)), np.float32)
    rels[name] = float(np.max(np.abs(
        np.asarray(d.stream(name, x)) - np.asarray(legacy.stream(x)))))

rng = np.random.default_rng(3)
for i in range(4):
    d.submit("mem", rng.uniform(0, 1, (3, 64)).astype(np.float32))
    d.submit("dig", rng.uniform(0, 1, (2, 32)).astype(np.float32))
d.run_until_drained()
s = d.stats()
rep = d.report()
print(json.dumps({
    "devices": len(jax.devices()),
    "n_chips": d.n_chips,
    "chip_systems": list(d.chip_systems),
    "app_chips": {"mem": d.app_chips("mem"), "dig": d.app_chips("dig")},
    "rels": rels,
    "lanes": {a: s.apps[a].lanes for a in s.apps},
    "report_rows": {a: rep.apps[a].n_chips for a in rep.apps},
    "report_total": rep.n_chips,
    "rollup_exact": (
        sum(a.requests for a in s.apps.values()) ==
        s.fleet.requests == 8 and
        sum(a.items for a in s.apps.values()) == s.fleet.items == 20
        and sum(a.lanes for a in s.apps.values()) == s.fleet.lanes),
}))
"""


def test_heterogeneous_two_device_subprocess(sim_subprocess):
    """Memristor and digital chips co-resident in one fleet: per-app
    single-chip submeshes, lanes scaled by the app's OWN chip count,
    report rows per submesh with the fleet total = the mesh size, and
    the stats roll-up exact across systems."""
    res = sim_subprocess(_HETERO_SCRIPT, n_devices=2)
    assert res["devices"] == 2 and res["n_chips"] == 2
    assert res["chip_systems"] == ["memristor", "digital"]
    assert res["app_chips"] == {"mem": 1, "dig": 1}
    assert res["rels"] == {"mem": 0.0, "dig": 0.0}
    # lanes_per_chip × the app's submesh size (1 chip each here)
    assert res["lanes"] == {"mem": 2, "dig": 4}
    assert res["report_rows"] == {"mem": 1, "dig": 1}
    assert res["report_total"] == 2
    assert res["rollup_exact"]


# ------------------------------------------------------------------- #
# multi-process deployment (behind the distributed marker)
# ------------------------------------------------------------------- #
_DIST_WORKER = """
import json, os
import numpy as np
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address="127.0.0.1:" + os.environ["REPRO_DIST_PORT"],
    num_processes=int(os.environ["REPRO_DIST_NPROCS"]),
    process_id=int(os.environ["REPRO_DIST_RANK"]))

from repro.chip import compile_chip
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.deploy import AppSpec, DeploymentSpec, deploy
from repro.launch.mesh import make_distributed_fleet_mesh

rank = jax.process_index()
spec_a = MLPSpec((64, 48, 10), activation="threshold",
                 out_activation="linear")
spec_b = MLPSpec((32, 16, 4), activation="threshold",
                 out_activation="linear")
pa = mlp_init(jax.random.PRNGKey(0), spec_a)
pb = mlp_init(jax.random.PRNGKey(7), spec_b)
mesh = make_distributed_fleet_mesh()
d = deploy(DeploymentSpec(apps=(
    AppSpec("alpha", spec_a, params=pa, lanes_per_chip=1),
    AppSpec("beta", spec_b, params=pb, system="digital",
            lanes_per_chip=1),
), mesh=mesh))
assert d.is_distributed

# stream_local == single chip on this rank's row block (SPMD: every
# rank calls with the same local row count)
chip_a = compile_chip(spec_a, params=pa)
n_local = jax.local_device_count()
B = 2 * mesh.devices.size
xg = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (B, 64)),
                np.float32)
lo = rank * 2 * n_local
x_local = xg[lo:lo + 2 * n_local]
y_local = np.asarray(d.stream("alpha", x_local))
with jax.default_device(jax.local_devices()[0]):
    ref = np.asarray(chip_a.stream(np.asarray(xg)))
rel = float(np.max(np.abs(y_local - ref[lo:lo + 2 * n_local])))

# lockstep multi-app drain: each rank submits its own traffic
rng = np.random.default_rng(100 + rank)
for i in range(2 + rank):
    d.submit("alpha", rng.uniform(0, 1, (2, 64)).astype(np.float32))
    d.submit("beta", rng.uniform(0, 1, (3, 32)).astype(np.float32))
d.run_until_drained()
local = d.stats()
glob = d.stats_global()
exact = (sum(a.requests for a in glob.apps.values()) ==
         glob.fleet.requests and
         sum(a.items for a in glob.apps.values()) == glob.fleet.items
         and sum(a.lanes for a in glob.apps.values()) ==
         glob.fleet.lanes)
# the lane CONTRACT, absolutely: each rank schedules
# lanes_per_chip x n_local_chips per app, the fleet-wide budget is
# lanes_per_chip x n_chips (NOT x n_processes more)
lanes_ok = (local.apps["alpha"].lanes == 1 * n_local and
            glob.apps["alpha"].lanes == 1 * mesh.devices.size)
print(json.dumps({"rank": rank, "rel": rel, "exact": bool(exact),
                  "lanes_ok": bool(lanes_ok),
                  "ok": rel == 0.0 and bool(exact) and bool(lanes_ok),
                  "local_requests": local.fleet.requests,
                  "global_requests": glob.fleet.requests,
                  "global_items": glob.fleet.items}))
jax.distributed.shutdown()
"""


@pytest.mark.distributed
def test_distributed_multiapp_deployment(launch_fleet):
    import sys

    from repro.launch import simdev

    results = launch_fleet([sys.executable, "-c", _DIST_WORKER], 2,
                           devices_per_process=2, timeout=600)
    assert [r.returncode for r in results] == [0, 0], \
        "\n".join(r.stderr[-1500:] for r in results)
    workers = [simdev.last_json_line(r.stdout) for r in results]
    for w in workers:
        assert w["ok"] and w["rel"] == 0.0 and w["exact"]
        assert w["lanes_ok"]
    # every rank reports the same exact fleet-wide roll-up, which
    # accounts for each host's own submissions (2 and 3 per app)
    g0 = workers[0]
    assert all(w["global_requests"] == g0["global_requests"]
               for w in workers)
    assert g0["global_requests"] == \
        sum(w["local_requests"] for w in workers) == 2 * (2 + 3)
