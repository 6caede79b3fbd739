"""repro.fleet — multi-chip streaming fabric, served through the router.

In-process tests run on the parent's single CPU device (a 1-chip fleet
must already be exact and serve correctly through a one-app
:class:`repro.deploy.MultiAppRouter`); the ≥2-device sharding equality
runs in a subprocess so XLA's host-device count can be pinned before
jax initializes (same pattern as test_elastic).
"""
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chip import ChipRateWarning, compile_chip
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.data.pipeline import SensorPipeline
from repro.deploy import DistributedMultiAppRouter, MultiAppRouter
from repro.fleet import BoundedQueue, StreamSource, shard_chip
from repro.serving.engine import ItemRequest
from repro.serving.stats import merge_stats

APP = "app"


def one_app_router(fleet, *, lanes_per_chip, queue_limit=None, **kw):
    """The router over one fleet member: ``lanes_per_chip × n_chips``
    lanes, one admission queue."""
    return MultiAppRouter({APP: fleet},
                          lanes={APP: lanes_per_chip * fleet.n_chips},
                          queue_limits={APP: queue_limit}, **kw)


def request(uid, items):
    return ItemRequest(uid=uid, items=items, key=APP)


@pytest.fixture(scope="module")
def chip():
    spec = MLPSpec((64, 32, 10), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    return compile_chip(spec, params=params)


# -------------------- sharded stream ---------------------------------- #
def test_one_chip_fleet_stream_is_exact(chip):
    fleet = shard_chip(chip, 1)
    x = jax.random.uniform(jax.random.PRNGKey(1), (13, 64))
    assert jnp.all(fleet.stream(x) == chip.stream(x))


def test_fleet_stream_pads_ragged_batches(chip):
    fleet = shard_chip(chip, 1)
    for b in (1, 2, 7):
        x = jax.random.uniform(jax.random.PRNGKey(b), (b, 64))
        y = fleet.stream(x)
        assert y.shape == (b, 10)
        assert jnp.all(y == chip.stream(x))


def test_fleet_rejects_analytic_chip():
    analytic = compile_chip((1, (8, 4)))
    with pytest.raises(ValueError, match="analytic-only"):
        shard_chip(analytic, 1)
    # the router guards a bare analytic chip the same way
    with pytest.raises(ValueError, match="analytic-only"):
        MultiAppRouter({APP: analytic}, lanes={APP: 2})


def test_fleet_requires_visible_devices(chip):
    with pytest.raises(ValueError, match="devices visible"):
        shard_chip(chip, len(jax.devices()) + 1)


def test_sharded_stream_matches_single_chip_across_devices(
        sim_subprocess):
    """The acceptance bar: ≥2 simulated devices, rel 0.0 vs the
    single-chip stream. Subprocess (via the shared conftest fixture):
    the device count must be pinned before jax initializes."""
    script = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.chip import compile_chip
        from repro.core.crossbar_layer import MLPSpec, mlp_init
        from repro.deploy import MultiAppRouter
        from repro.fleet import shard_chip
        from repro.serving.engine import ItemRequest
        import numpy as np

        spec = MLPSpec((784, 200, 100, 10), activation="threshold",
                       out_activation="linear")
        params = mlp_init(jax.random.PRNGKey(0), spec)
        chip = compile_chip(spec, params=params)
        fleet = shard_chip(chip)
        x = jax.random.uniform(jax.random.PRNGKey(1), (11, 784))
        rel = float(jnp.max(jnp.abs(fleet.stream(x) - chip.stream(x))))
        # the process-local scatter/gather must agree with the
        # host-global path on one process (its multi-process semantics
        # are pinned by the distributed suite)
        local_same = bool(np.array_equal(fleet.stream_local(np.asarray(x)),
                                         fleet.stream_host(np.asarray(x))))
        # routed serving must match the direct stream too
        router = MultiAppRouter({"app": fleet},
                                lanes={"app": 2 * fleet.n_chips})
        rng = np.random.default_rng(0)
        for i in range(5):
            router.submit(ItemRequest(uid=i,
                                      items=rng.uniform(0, 1,
                                                        (2 + i, 784)),
                                      key="app"))
        done = router.run_until_drained()
        served_ok = all(
            np.allclose(st.result,
                        np.asarray(chip.stream(
                            jnp.asarray(st.request.items))), atol=1e-5)
            for st in done)
        print(json.dumps({"devices": len(jax.devices()), "rel": rel,
                          "drained": len(done), "local_same": local_same,
                          "served_ok": served_ok}))
    """)
    res = sim_subprocess(script, n_devices=2)
    assert res["devices"] == 2
    assert res["rel"] == 0.0          # exact, not approximately equal
    assert res["drained"] == 5 and res["served_ok"]
    assert res["local_same"]


# -------------------- router ------------------------------------------ #
def test_router_drains_and_matches_stream(chip):
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=3)
    rng = np.random.default_rng(1)
    reqs = [request(i, rng.uniform(-1, 1, (1 + i, 64)))
            for i in range(6)]
    for r in reqs:
        assert router.submit(r)
    done = router.run_until_drained()
    assert sorted(st.request.uid for st in done) == list(range(6))
    for st in done:
        want = np.asarray(chip.stream(jnp.asarray(st.request.items,
                                                  jnp.float32)))
        np.testing.assert_allclose(st.result, want, atol=1e-5)


def test_router_admission_control(chip):
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=2, queue_limit=2)
    rng = np.random.default_rng(2)
    results = [router.submit(request(i, rng.uniform(0, 1, (2, 64))))
               for i in range(5)]
    assert results == [True, True, False, False, False]
    assert router.rejected == 3
    router.step()                     # admits 2 into lanes, queue frees
    assert router.submit(request(9, rng.uniform(0, 1, (2, 64))))


def test_router_latency_accounting(chip):
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=2)
    rng = np.random.default_rng(3)
    for i in range(4):
        router.submit(request(i, rng.uniform(0, 1, (3, 64))))
    done = router.run_until_drained()
    for st in done:
        assert st.request.t_submit <= st.t_admit <= st.t_first \
            <= st.t_done
        assert st.done_step >= st.admit_step
    stats = router.stats_app(APP)
    assert stats.requests == 4 and stats.items == 12
    assert stats.items_per_second > 0
    assert 0 < stats.occupancy <= 1
    assert stats.latency_s_p95 >= stats.latency_s_p50 > 0
    # 2 lanes x 4 requests of 3 items: the two late requests queue
    # behind the first two, so their wait exceeds the first pair's
    waits = [st.wait_s for st in sorted(done,
                                        key=lambda s: s.request.uid)]
    assert max(waits[2:]) >= max(waits[:2])


# -------------------- sensor-stream frontend -------------------------- #
def test_bounded_queue_backpressure():
    q = BoundedQueue(2)
    assert q.offer(1) and q.offer(2)
    assert not q.offer(3)             # full: producer must back off
    assert q.full and len(q) == 2
    assert q.poll() == 1
    assert q.offer(3)                 # space freed
    assert [q.poll(), q.poll(), q.poll()] == [2, 3, None]


def test_sensor_pipeline_rejects_bad_geometry():
    with pytest.raises(ValueError, match="window"):
        SensorPipeline(window=96, height=64, width=64)
    with pytest.raises(ValueError, match="stride"):
        SensorPipeline(window=8, height=16, width=16, stride=0)


def test_sensor_pipeline_is_pure_function_of_step():
    pipe = SensorPipeline(window=8, stride=8, height=16, width=16)
    assert pipe.d_item == 64 and pipe.windows_per_frame == 4
    b0, b0_again = pipe.batch(0), pipe.batch(0)
    assert jnp.all(b0 == b0_again)
    assert not bool(jnp.all(pipe.batch(1) == b0))
    assert b0.shape == (4, 64)
    assert float(b0.min()) >= 0.0 and float(b0.max()) <= 1.0


def test_stream_source_backpressure_and_drain(chip):
    pipe = SensorPipeline(window=8, stride=8, height=16, width=16)
    src = StreamSource(pipe, n_requests=10, capacity=3)
    assert src.pump() == 3 and src.queue.full
    assert src.pump() == 0 and src.stalls == 2
    taken = [src.take() for _ in range(3)]
    assert [t.uid for t in taken] == [0, 1, 2]
    assert src.pump() == 3            # refills after consumption
    while not src.exhausted:
        src.pump()
        src.take()
    assert src.produced == 10 and src.taken == 10


def test_router_serve_rejects_zero_capacity_queue(chip):
    """queue_limit=0 can never admit, so serve() must refuse up front
    instead of spinning (max_steps bounds iterations regardless)."""
    pipe = SensorPipeline(window=8, stride=8, height=16, width=16)
    src = StreamSource(pipe, n_requests=3, capacity=2)
    router = one_app_router(shard_chip(chip, 1), lanes_per_chip=2,
                            queue_limit=0)
    with pytest.raises(ValueError, match="queue_limit"):
        router.serve({APP: src}, max_steps=5)


def test_stream_host_matches_stream(chip):
    fleet = shard_chip(chip, 1)
    x = np.random.default_rng(5).uniform(-1, 1, (5, 64)) \
        .astype(np.float32)
    host = fleet.stream_host(x)
    assert isinstance(host, np.ndarray)
    np.testing.assert_array_equal(host, np.asarray(fleet.stream(x)))


def test_router_serve_loop_end_to_end(chip):
    """The closed sensor→router loop: every produced window is served
    and matches the direct stream, under bounded queues on both sides."""
    pipe = SensorPipeline(window=8, stride=8, height=16, width=16)
    src = StreamSource(pipe, n_requests=7, capacity=2)
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=2, queue_limit=3)
    done = router.serve({APP: src})
    assert len(done) == 7 and src.exhausted
    for st in done:
        want = np.asarray(chip.stream(jnp.asarray(st.request.items)))
        np.testing.assert_allclose(st.result, want, atol=1e-5)


# -------------------- multi-process surfaces, 1-process semantics ----- #
def test_stream_local_matches_stream_host(chip):
    """On one process the process-local scatter/gather is the whole
    scatter/gather; ragged batches included (padding happens against
    the LOCAL chip count)."""
    fleet = shard_chip(chip, 1)
    for b in (1, 3, 8):
        x = np.random.default_rng(b).uniform(-1, 1, (b, 64)) \
            .astype(np.float32)
        np.testing.assert_array_equal(fleet.stream_local(x),
                                      fleet.stream_host(x))
    assert fleet.n_local_chips == fleet.n_chips == 1
    assert not fleet.is_distributed


def test_distributed_router_requires_distributed_fleet(chip):
    with pytest.raises(ValueError, match="span processes"):
        DistributedMultiAppRouter({APP: shard_chip(chip, 1)},
                                  lanes={APP: 2})


def test_stream_source_for_host_partitions_the_stream(chip):
    """Host h of H takes pipeline steps h, h+H, …: the per-host feeds
    are disjoint, cover the stream, and replay exactly (purity)."""
    pipe = SensorPipeline(window=8, stride=8, height=16, width=16)
    hosts = 3
    feeds = {}
    for h in range(hosts):
        src = StreamSource.for_host(pipe, host=h, hosts=hosts,
                                    n_requests=4, capacity=8)
        src.pump()
        reqs = [src.take() for _ in range(4)]
        feeds[h] = reqs
        # uids are globally unique without coordination
        assert [r.uid for r in reqs] == [h * 1_000_000 + i
                                         for i in range(4)]
    for h, reqs in feeds.items():
        for i, r in enumerate(reqs):
            step = h + i * hosts            # the step this host drew
            np.testing.assert_array_equal(
                r.items, np.asarray(pipe.batch(step), np.float32))
    with pytest.raises(ValueError, match="host"):
        StreamSource.for_host(pipe, host=3, hosts=3)
    with pytest.raises(ValueError, match="step_stride"):
        StreamSource(pipe, step_stride=0)


def test_router_step_when_idle_keeps_stepping(chip):
    """The SPMD lockstep hook: an idle engine still runs the batched
    step (zero rows) so a multi-process collective can't deadlock on a
    locally drained rank."""
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=2, step_when_idle=True)
    assert router.step() == 0 and router.steps == 1   # idle, but ran
    router.submit(request(
        0, np.random.default_rng(0).uniform(0, 1, (2, 64))))
    router.run_until_drained()
    idle = one_app_router(fleet, lanes_per_chip=2)    # default: skip
    assert idle.step() == 0 and idle.steps == 0


def test_merge_stats_rolls_up_counters(chip):
    fleet = shard_chip(chip, 1)
    rng = np.random.default_rng(7)

    def run_router(n_req):
        router = one_app_router(fleet, lanes_per_chip=2)
        for i in range(n_req):
            router.submit(request(i, rng.uniform(0, 1, (2, 64))))
        router.run_until_drained()
        return router.stats().fleet

    a, b = run_router(2), run_router(3)
    m = merge_stats([a, b])
    assert m.requests == 5 and m.items == 10
    assert m.lanes == a.lanes + b.lanes
    assert m.steps == max(a.steps, b.steps)
    assert m.wall_s == max(a.wall_s, b.wall_s)
    assert m.rejected == 0
    assert m.latency_s_p95 == max(a.latency_s_p95, b.latency_s_p95)
    assert m.items_per_second == pytest.approx(10 / m.wall_s)
    # single-host merge keeps the counters (percentiles by definition)
    one = merge_stats([a])
    assert (one.requests, one.items, one.lanes) == \
        (a.requests, a.items, a.lanes)
    empty = merge_stats([])
    assert empty.requests == 0 and empty.items == 0


def test_fleet_level_rate_validation(chip):
    """compile-time validation vouches for ONE chip; the fleet target
    must be re-validated against replication × n_chips fabric copies
    (the capacity the fleet actually multiplies)."""
    per_chip = chip.route.max_items_per_second * chip.replication
    # a fleet-feasible target is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", ChipRateWarning)
        shard_chip(chip, 1, items_per_second=0.9 * per_chip)
    # an infeasible fleet target warns ...
    with pytest.warns(ChipRateWarning, match="shard_chip.*infeasible"):
        shard_chip(chip, 1, items_per_second=1e3 * per_chip)
    # ... and raises under strict_rate
    with pytest.raises(ValueError, match="infeasible"):
        shard_chip(chip, 1, items_per_second=1e3 * per_chip,
                   strict_rate=True)


# -------------------- fleet report ------------------------------------ #
def test_fleet_report_composes_chip_report(chip):
    fleet = shard_chip(chip, 1)
    router = one_app_router(fleet, lanes_per_chip=2)
    rng = np.random.default_rng(4)
    for i in range(3):
        router.submit(request(i, rng.uniform(0, 1, (2, 64))))
    router.run_until_drained()
    rep = fleet.report(router.stats_app(APP))
    chip_rep = chip.report()
    assert rep.n_chips == 1
    assert rep.cores == chip_rep.cores
    assert rep.area_mm2 == pytest.approx(chip_rep.area_mm2)
    assert rep.power_mw == pytest.approx(chip_rep.power_mw)
    assert rep.energy_per_item_nj == \
        pytest.approx(chip_rep.energy_per_item_nj)
    assert rep.capacity_items_per_second == pytest.approx(
        chip_rep.capacity_items_per_second * chip_rep.replication)
    # both rate roll-ups scale by replication x chips alike
    assert rep.routing_limited_items_per_second == pytest.approx(
        chip_rep.routing_limited_items_per_second *
        chip_rep.replication)
    assert rep.served is not None and rep.served.items == 6
    assert rep.served_fraction_of_capacity == pytest.approx(
        rep.served.items_per_second / rep.capacity_items_per_second)
    assert "FleetReport" in str(rep) and "served" in str(rep)
