"""The unified chip API: compile → program → stream as one object.

Property tests that ``chip.stream`` executes the *mapped* dataflow
(row-chunk sub-neurons, Fig. 11 combiner levels, replica fan-out) yet
matches the programmed dense oracle; that ``chip.report`` agrees with
the independent costmodel assembly the Tables II–VI benchmark validates
against the paper; and that the TDM slot schedule every compile carries
is conflict-free per link.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import assume, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:         # property tests skip; parametrized cases run
    HAVE_HYPOTHESIS = False

from repro.chip import ChipRateWarning, CompiledChip, compile_chip
from repro.configs.paper_apps import APPS
from repro.core.costmodel import specialized_cost
from repro.core.crossbar_layer import (MLPSpec, mlp_init, program_mlp,
                                       programmed_mlp_apply)
from repro.core.neural_core import CoreGeometry
from repro.deploy import AppSpec, deploy


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) /
                 jnp.maximum(jnp.max(jnp.abs(b)), 1e-12))


def _oracle(params, spec, geom, mode, x):
    prog = program_mlp(params, spec, mode=mode, geom=geom)
    return programmed_mlp_apply(prog, x)


# The mapped stream sums per-row-chunk partials through the combiner
# neurons; the dense oracle contracts every row at once. Same products,
# another float32 summation order: over a few hundred terms the two
# differ by ~1e-6 relative. Hidden threshold activations turn such a
# difference into a ±1 flip when a pre-activation lies that close to
# zero, and one flip moves the output by a whole weight row. So rows
# with an oracle pre-activation within THRESHOLD_MARGIN·max|z| of the
# switch point (ten times the summation-order error) are left out of
# the float comparison; the rest must agree to STREAM_RTOL.
THRESHOLD_MARGIN = 1e-5
STREAM_RTOL = 1e-5


def _near_threshold(prog, x):
    """Rows of x whose oracle pre-activation at any threshold layer
    lies within THRESHOLD_MARGIN·max|z| of zero."""
    near = np.zeros(x.shape[0], bool)
    for i, act in enumerate(prog.activations):
        if act != "threshold":
            continue
        head = dataclasses.replace(
            prog, layers=prog.layers[:i + 1], biases=prog.biases[:i + 1],
            activations=prog.activations[:i] + ("linear",))
        z = np.abs(np.asarray(programmed_mlp_apply(head, x)))
        near |= np.any(z <= THRESHOLD_MARGIN * z.max(), axis=1)
    return near


# -------------------- stream == dense oracle -------------------------- #
def _check_stream_vs_oracle(dims, geom, batch):
    """chip.stream evaluates per-row-chunk partials through programmed
    combiner neurons (the mapped Fig. 11 dataflow), yet must agree with
    the dense programmed oracle to float tolerance for any geometry.
    Returns how many rows were compared (see THRESHOLD_MARGIN)."""
    geom = CoreGeometry(*geom)
    spec = MLPSpec(tuple(dims), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(hash(tuple(dims)) % 2**31), spec)
    chip = compile_chip(spec, params=params, geom=geom)
    x = jax.random.uniform(jax.random.PRNGKey(batch), (batch, dims[0]),
                           minval=-1, maxval=1)
    y = chip.stream(x)
    assert y.shape == (batch, dims[-1])
    assert bool(jnp.all(jnp.isfinite(y)))
    prog = program_mlp(params, spec, mode="crossbar", geom=geom)
    keep = ~_near_threshold(prog, x)
    if keep.any():
        assert _rel(y[keep], programmed_mlp_apply(prog, x)[keep]) \
            <= STREAM_RTOL
    return int(keep.sum())


@pytest.mark.parametrize("dims,geom,batch", [
    ((784, 200, 100, 10), (128, 64), 128),   # the deep app, split (R=7)
    ((784, 200, 100, 10), (256, 128), 32),   # same net, DSE geometry
    ((9, 20, 2), (128, 64), 17),             # edge app: no splitting
    ((300, 64, 5), (16, 8), 5),              # R=19 > 16 rows: Fig. 11
                                             # multi-level combiner
    ((65, 3), (32, 16), 1),                  # single layer, 1-col tile
])
def test_stream_matches_oracle(dims, geom, batch):
    assert _check_stream_vs_oracle(dims, geom, batch) == batch


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.lists(st.integers(3, 300), min_size=2, max_size=4),
           st.sampled_from([(16, 8), (32, 16), (128, 64)]),
           st.integers(1, 17))
    def test_stream_matches_oracle_across_geometries(dims, geom, batch):
        assume(_check_stream_vs_oracle(dims, geom, batch) > 0)


def test_stream_fig11_multi_level_combiner():
    """d_in >> geom.rows² forces the Fig. 11 recursion: more partials
    than a core has rows, so an intermediate sub-neuron level combines
    before the final combining neuron."""
    geom = CoreGeometry(8, 8)
    dims = (600, 5, 3)                    # 75 chunks > 8 rows → 2 levels
    spec = MLPSpec(dims, activation="sigmoid", out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(3), spec)
    chip = compile_chip(spec, params=params, geom=geom)
    layer0 = chip.plan[0]
    # 75 chunks → (10, 8) sub-neuron groups → (2, 5) → (1, 2) combiner
    assert len(layer0.levels) >= 2        # sub-neuron level(s) + combiner
    assert layer0.levels[0][0] * layer0.levels[0][1] >= \
        math.ceil(dims[0] / geom.rows)
    assert layer0.levels[-1][0] == 1      # final combining neuron
    x = jax.random.uniform(jax.random.PRNGKey(4), (9, dims[0]))
    assert _rel(chip.stream(x),
                _oracle(params, spec, geom, "crossbar", x)) <= 1e-5


def test_stream_digital_system():
    spec = MLPSpec((100, 40, 10), activation="sigmoid",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(5), spec)
    chip = compile_chip(spec, params=params, system="digital")
    x = jax.random.uniform(jax.random.PRNGKey(6), (13, 100))
    oracle = programmed_mlp_apply(
        program_mlp(params, spec, mode="digital"), x)
    assert _rel(chip.stream(x), oracle) <= 1e-6


def test_stream_from_programmed_mlp_is_exact():
    """Compiling from an already-programmed MLP reuses its tile state
    (the very same arrays), so the mapped stream differs from the dense
    oracle only by float32 summation order (see STREAM_RTOL); no row of
    this seeded batch sits near a threshold."""
    spec = MLPSpec((784, 200, 100, 10), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    prog = program_mlp(params, spec, mode="crossbar")
    chip = compile_chip(prog)
    assert all(a is b for a, b in zip(
        jax.tree.leaves([l.tiles for l in chip.plan]),
        jax.tree.leaves(prog.layers)))
    x = jax.random.uniform(jax.random.PRNGKey(1), (32, 784))
    assert not _near_threshold(prog, x).any()
    assert _rel(chip.stream(x), programmed_mlp_apply(prog, x)) \
        <= STREAM_RTOL


def test_stream_replica_fanout_matches_single_replica():
    """items_per_second sizing replicates the pipeline (§V.C); dealing
    the batch across identical programmed replicas must not change any
    output, including when the batch doesn't divide evenly."""
    spec = MLPSpec((64, 24, 4), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(7), spec)
    probe = compile_chip(spec, params=params)   # one replica's capacity
    rate = 3.5 * probe.mapping.items_per_second_capacity
    chip = compile_chip(spec, params=params, items_per_second=rate)
    assert chip.replication > 1
    x = jax.random.uniform(jax.random.PRNGKey(8),
                           (3 * chip.replication + 1, 64))
    np.testing.assert_allclose(np.asarray(chip.stream(x, fan_out=True)),
                               np.asarray(chip.stream(x, fan_out=False)),
                               rtol=1e-6, atol=1e-6)


def test_chip_is_jitable_pytree():
    """A CompiledChip jits as an argument: array leaves (tiles, scales,
    biases) trace, geometry/mapping/schedule ride as static aux data —
    and the static wrapper is stable per chip, so repeated calls reuse
    ONE trace (re-trace per compile, never per call)."""
    spec = MLPSpec((50, 20, 5), activation="sigmoid",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(9), spec)
    chip = compile_chip(spec, params=params)

    traces = []

    @jax.jit
    def run(c: CompiledChip, x):
        traces.append(1)
        return c.stream(x)

    x = jax.random.uniform(jax.random.PRNGKey(10), (4, 50))
    np.testing.assert_allclose(np.asarray(run(chip, x)),
                               np.asarray(chip.stream(x)),
                               rtol=1e-6, atol=1e-6)
    run(chip, x)
    run(chip, x)
    assert len(traces) == 1, "same chip must not retrace per call"
    leaves = jax.tree.leaves(chip)
    assert leaves and all(hasattr(l, "dtype") for l in leaves)
    # flatten/unflatten round-trip preserves the trace key
    flat, treedef = jax.tree.flatten(chip)
    run(jax.tree.unflatten(treedef, flat), x)
    assert len(traces) == 1


@pytest.mark.parametrize("system", ["memristor", "digital"])
def test_stream_use_kernel_interpret_matches_jnp_path(system):
    """chip.stream(use_kernel=True) runs the fused Pallas kernels (CPU
    interpret mode here) per row chunk; it must agree with the jnp
    tile-grid path on both systems."""
    spec = MLPSpec((200, 50, 10), activation="sigmoid",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(21), spec)
    chip = compile_chip(spec, params=params, system=system)
    x = jax.random.uniform(jax.random.PRNGKey(22), (8, 200),
                           minval=-1, maxval=1)
    y_k = chip.stream(x, use_kernel=True)
    y_j = chip.stream(x, use_kernel=False)
    assert y_k.shape == (8, 10)
    assert _rel(y_k, y_j) <= 1e-5


def test_analytic_chip_streams_nothing_but_reports():
    chip = compile_chip((1, (784, 200, 100, 10)))
    with pytest.raises(ValueError, match="analytic-only"):
        chip.stream(jnp.zeros((1, 784)))
    rep = chip.report()
    assert rep.cores == chip.total_cores > 0


# -------------------- report == costmodel ----------------------------- #
@pytest.mark.parametrize("app_id", list(APPS))
@pytest.mark.parametrize("system", ["memristor", "digital"])
def test_report_reproduces_tables_accounting(app_id, system):
    """chip.report() must reproduce the per-app numbers the Tables
    II–VI benchmark assembles from mapping+routing+costmodel by hand."""
    app = APPS[app_id]
    nets = app.memristor_nets if system == "memristor" else app.sram_nets
    chip = compile_chip(nets, system=system,
                        items_per_second=app.items_per_second,
                        sensor_flags=app.sensor_flags(system),
                        deps=app.net_deps(system),
                        tsv_bits_per_item=app.tsv_bits_per_item)
    ref = specialized_cost(app, system)
    rep = chip.report()
    assert rep.cores == ref.cores
    assert rep.area_mm2 == pytest.approx(ref.area_mm2, rel=1e-12)
    assert rep.power_mw == pytest.approx(ref.power_mw, rel=1e-12)
    assert rep.energy_per_item_nj == \
        pytest.approx(ref.energy_per_item_nj, rel=1e-12)
    assert rep.power_mw == pytest.approx(
        rep.leak_mw + rep.compute_mw + rep.routing_mw + rep.tsv_mw)


# -------------------- TDM schedule feasibility ------------------------ #
def _assert_schedule_conflict_free(route):
    import math

    from repro.core.routing import LINK_BITS
    for link, entries in route.schedule.items():
        spans = sorted((start, start + n) for _, start, n in entries)
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert a1 <= b0, f"slot overlap on link {link}"
        # the link's TDM frame is exactly the sum of its flows' slots
        # (no holes, no double-booking) and covers the link's bit load
        assert spans[-1][1] == sum(n for _, _, n in entries)
        assert spans[-1][1] >= math.ceil(route.link_bits[link] /
                                         LINK_BITS)


@pytest.mark.parametrize("app_id", list(APPS))
@pytest.mark.parametrize("system", ["memristor", "digital"])
def test_route_schedule_no_slot_overlap_paper_apps(app_id, system):
    app = APPS[app_id]
    nets = app.memristor_nets if system == "memristor" else app.sram_nets
    chip = compile_chip(nets, system=system,
                        items_per_second=app.items_per_second,
                        sensor_flags=app.sensor_flags(system),
                        deps=app.net_deps(system))
    _assert_schedule_conflict_free(chip.route)


@pytest.mark.parametrize("nets", [
    [(2, (784, 200, 10)), (1, (9, 20, 2))],        # mixed app
    [(3, (1024, 256, 64, 8))],                     # replicated deep net
    [(1, (48, 4)), (1, (4000, 100, 10)), (2, (130, 130, 130))],
])
def test_route_schedule_no_slot_overlap_mixed_nets(nets):
    """Slot assignments never overlap per link for arbitrary app mixes,
    not just the paper's five."""
    chip = compile_chip(nets)
    _assert_schedule_conflict_free(chip.route)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 3),
                              st.lists(st.integers(1, 800),
                                       min_size=2, max_size=4)),
                    min_size=1, max_size=3))
    def test_route_schedule_no_slot_overlap_random_nets(nets):
        chip = compile_chip([(i, tuple(d)) for i, d in nets])
        _assert_schedule_conflict_free(chip.route)


# -------------------- serving: a one-chip deploy() ------------------- #
def _one_chip(spec, params, lanes):
    return deploy(AppSpec("app", spec, params=params,
                          lanes_per_chip=lanes), n_chips=1)


def test_serve_drains_and_matches_stream():
    spec = MLPSpec((30, 16, 4), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(11), spec)
    d = _one_chip(spec, params, lanes=2)
    chip = d.chip("app")
    rng = np.random.default_rng(12)
    for i in range(5):
        assert d.submit("app", rng.uniform(-1, 1, (1 + i, 30)))
    done = d.run_until_drained()
    assert len(done) == 5
    assert sorted(st_.request.uid for st_ in done) == list(range(5))
    for st_ in done:
        want = np.asarray(chip.stream(jnp.asarray(st_.request.items,
                                                  jnp.float32)))
        np.testing.assert_allclose(st_.result, want, atol=1e-5)


def test_serve_rejects_analytic_chip():
    d = deploy(AppSpec("app", (1, (8, 4))), n_chips=1)
    assert d.chip("app").plan is None and d.router is None
    with pytest.raises(ValueError, match="analytic-only"):
        d.submit("app", np.zeros((1, 8), np.float32))


def test_serve_backfills_ragged_arrivals_without_starvation():
    """Ragged mid-stream arrivals must backfill freed lanes while a
    long-running stream stays resident: the long request never starves
    the shorts, the shorts never evict the long one, and every freed
    lane is reused within one step."""
    spec = MLPSpec((30, 16, 4), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(23), spec)
    d = _one_chip(spec, params, lanes=2)
    chip, eng = d.chip("app"), d.router
    rng = np.random.default_rng(24)
    long = eng.submit_app("app", rng.uniform(-1, 1, (20, 30)))
    eng.step()                          # long resident, one lane free
    # ragged arrivals while the long stream is mid-flight
    shorts = [eng.submit_app("app", rng.uniform(-1, 1, (2 + i % 3, 30)))
              for i in range(5)]
    while len(eng.finished) < len(shorts):
        had_waiting = bool(eng.queue)
        emitted = eng.step()
        assert emitted > 0
        # a step that began with work waiting must stream a FULL lane
        # set: freed lanes are backfilled before streaming, never idled
        if had_waiting:
            assert emitted == eng.slots
    # all shorts retired while the long request is STILL streaming
    assert {st.request.uid for st in eng.finished} == \
        {r.uid for r in shorts}
    assert long.uid in {st.request.uid for st in eng.active.values()}
    done = eng.run_until_drained()
    assert len(done) == 6
    for st in done:
        want = np.asarray(chip.stream(jnp.asarray(st.request.items,
                                                  jnp.float32)))
        np.testing.assert_allclose(st.result, want, atol=1e-5)
    # per-request accounting survived the churn
    for st in done:
        assert st.result.shape[0] == st.request.items.shape[0]
        assert st.t_done >= st.t_admit >= st.request.t_submit


# -------------------- compile-time rate validation -------------------- #
def test_rate_validation_feasible_is_silent():
    """A routable target rate must compile without ChipRateWarning."""
    import warnings as w

    spec = MLPSpec((784, 200, 100, 10), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(25), spec)
    with w.catch_warnings():
        w.simplefilter("error", ChipRateWarning)
        chip = compile_chip(spec, params=params,
                            items_per_second=1e4)
    assert chip.items_per_second == 1e4


def test_rate_validation_infeasible_warns_and_strict_raises():
    """The deep app's compute capacity exceeds its routed TDM limit, so
    a rate that drives every replica at compute capacity is un-routable:
    compile warns by default and raises under strict_rate."""
    import warnings as w

    spec = MLPSpec((784, 200, 100, 10), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(25), spec)
    probe = compile_chip(spec, params=params)
    cap = probe.mapping.items_per_second_capacity
    limit = probe.route.max_items_per_second
    assert cap > limit                # precondition for infeasibility
    with pytest.warns(ChipRateWarning, match="infeasible"):
        compile_chip(spec, params=params, items_per_second=cap)
    with pytest.raises(ValueError, match="TDM"):
        compile_chip(spec, params=params, items_per_second=cap,
                     strict_rate=True)
