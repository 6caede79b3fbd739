"""CPU rehearsals of the benchmark harness.

Each cell of ``BENCHMARK.json`` runs end to end here at a tiny window on
the CPU backend, at the size its tenant's ``rehearsal`` names (the
sensor tenant's cells at their own), and so does the serve cell that
waits for its knee sweep (``held_cells.json``), laid over
``BENCHMARK.json`` by the ``harness`` fixture. The harness's look for
a TPU is steered in the test (``run.require_devices``), and so is its
compile-cache setup, which would otherwise change a process-wide JAX
setting of the test worker.
"""
import contextlib
import io
import json
import os

import jax
import numpy as np
import pytest

from bench import catalog, loops, reference, run, traffic, weights, workcount

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}
TENANT_FUNCTIONS = ("make_params", "deploy", "make_load", "warm", "window",
                    "check", "flops", "min_call_seconds", "rehearsal")
with open(os.path.join(os.path.dirname(__file__), "held_cells.json")) as f:
    HELD = json.load(f)


def with_held(bench: dict) -> dict:
    """``bench`` with the held cells and their metric entries added."""
    out = dict(bench, workloads=bench["workloads"] + HELD["workloads"])
    for kind in ("end_to_end", "per_layer"):
        entries = {m["name"]: dict(m) for m in bench[kind]}
        for m in HELD[kind]:
            if m["name"] in entries:
                entries[m["name"]]["workloads"] = \
                    entries[m["name"]]["workloads"] + m["workloads"]
            else:
                entries[m["name"]] = m
        out[kind] = list(entries.values())
    return out


def rehearse(run_cell):
    """``run_cell`` at the tenant's CPU rehearsal size
    (``tenant.rehearsal``), the cell's own where the tenant says so."""
    def go(bench, cell, cfg, mix, tenant, *rest):
        return run_cell(bench, cell, *tenant.rehearsal(cfg, mix), tenant,
                        *rest)
    return go


ONE_CHIP_CELLS = [c["name"] for c in with_held(catalog.benchmark())
                  ["workloads"] if c["chips"] == 1]


@pytest.fixture
def harness(monkeypatch):
    """``run.main`` on the CPU, the held cells included: returns (rc,
    last stdout line as JSON, stderr)."""
    real_benchmark, real_mix = catalog.benchmark, catalog.mix

    def mix(name, cell_name=None, **kw):
        params = real_mix(name, cell_name, **kw)
        if cell_name in HELD["rates"]:
            params["rate_per_s"] = HELD["rates"][cell_name]
        return params

    monkeypatch.setattr(catalog, "benchmark",
                        lambda *a: with_held(real_benchmark(*a)))
    monkeypatch.setattr(catalog, "mix", mix)
    monkeypatch.setattr(run, "configure_jax", lambda: None)
    monkeypatch.setattr(run, "require_devices", lambda chips: jax.devices())
    monkeypatch.setattr(run, "run_cell", rehearse(run.run_cell))

    def go(cell, seconds=0.2, trace=0, seed=2**40 + 3):
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
        lines = out.getvalue().strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

    return go


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_cell_runs_end_to_end(harness, cell):
    rc, res, err = harness(cell)
    assert rc == 0, err
    assert set(res) == CONTRACT_KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    bench = catalog.benchmark()
    want = {m["name"] for m in catalog.metrics_for(bench, cell,
                                                   "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert "compiles in window: 0" in err
    # the numbers compared are the last stderr lines, beside their limits
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_traced_run_reports_per_layer_metrics(harness):
    rc, res, err = harness("deep-1t1m.serve", trace=1)
    assert rc == 0, err
    assert set(res) <= CONTRACT_KEYS | {"breakdown"}
    assert list(res)[-1] == "checks"
    # the CPU has no device plane: the trace readers find nothing and
    # their metrics are left out, never reported as 0
    assert set(res["metrics"]) == {"setup.deploy_s",
                                   "router.host_ms_per_step",
                                   "member.ms_per_call.serve"}
    assert res["metrics"]["router.host_ms_per_step"]["value"] > 0


def test_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(run, "configure_jax", lambda: None)
    rc = run.main(["--workload", "object-sram.bulk", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def _break(monkeypatch, fn):
    """Put ``fn(out, x) -> out`` on every stream program the
    deployment traces from here on."""
    import repro.fleet.shard as shard

    real = shard.stream_pipeline

    def broken(plan, x, **kw):
        return fn(real(plan, x, **kw), x)

    monkeypatch.setattr(shard, "stream_pipeline", broken)


@pytest.mark.parametrize("cell", ["deep-1t1m.serve", "object-sram.bulk"])
def test_altered_answer_fails(harness, monkeypatch, cell):
    _break(monkeypatch, lambda out, x: out.at[0, 0].add(0.5))
    rc, res, _ = harness(cell)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > \
        res["checks"]["rel_err"]["limit"]


def test_half_batch_left_out_fails(harness, monkeypatch):
    def half(out, x):
        keep = np.arange(out.shape[0]) < out.shape[0] // 2
        return out * keep[:, None]

    _break(monkeypatch, half)
    rc, res, _ = harness("object-sram.bulk")
    assert rc == 0 and res["correct"] is False


def test_step_without_progress_fails(harness, monkeypatch):
    from repro.deploy.deployment import Deployment

    real_mix = catalog.mix
    monkeypatch.setattr(catalog, "mix", lambda *a, **k: dict(
        real_mix(*a, **k), drain_s=0.3))
    real_step = Deployment.step
    calls = {"n": 0}

    def stuck(self):
        calls["n"] += 1
        # the warm-up drains normally; in the window nothing advances
        return real_step(self) if calls["n"] <= 40 else 0

    monkeypatch.setattr(Deployment, "step", stuck)
    monkeypatch.setattr(Deployment, "run_until_drained",
                        lambda self, max_steps=10_000: [
                            real_step(self) for _ in range(40)])
    rc, res, _ = harness("deep-1t1m.serve")
    assert rc == 0 and res["correct"] is False and res["failed"] > 0


@pytest.mark.parametrize("cell", ["deep-1t1m.serve", "object-sram.bulk"])
def test_control_fails_the_limit(cell):
    """The reference one step lower in precision, in the program's
    place, reads above the limit (a small pool stands in for the
    cell's)."""
    cfg = catalog.config(catalog.cell(with_held(catalog.benchmark()),
                                      cell)["config"])
    params = weights.make(cfg, 7)
    x = traffic.sensor_items(traffic.rng(7, 3), 2048, cfg["dims"][0], 8)
    ref, near = reference.forward(cfg, params, x)
    ctl = reference.control_outputs(cfg, params, x)
    limits = cfg["check"]["limits"]
    assert all(v == 0.0 for v in reference.errors(ref, ref, near).values())
    read = reference.errors(ctl, ref, near)
    assert any(read[k] > limits[k] for k in limits)


def test_weights_lie_on_the_configured_grid():
    for name in ("deep-1t1m", "object-sram"):
        cfg = catalog.config(name)
        params = weights.make(cfg, 2**35 + 1)
        again = weights.make(cfg, 2**35 + 1)
        rows, cols, levels = weights.grid_of(cfg)
        for p, q in zip(params, again):
            w = np.asarray(p["w"])
            assert np.array_equal(w, np.asarray(q["w"]))
            r = rows or w.shape[0]
            t = w[:r, :cols]
            k = t / np.abs(t).max() * levels
            assert np.allclose(k, np.rint(k), atol=1e-3)


def test_serve_schedule_is_a_pure_function_of_the_seed():
    mix = dict(catalog.mix("serve"),
               rate_per_s=HELD["rates"]["deep-1t1m.serve"])
    a = traffic.open_schedule(mix, 2**33 + 5, 2.0, 784)
    b = traffic.open_schedule(mix, 2**33 + 5, 2.0, 784)
    c = traffic.open_schedule(mix, 2**33 + 6, 2.0, 784)
    for f in ("t", "start", "n", "pool"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.t, c.t)
    # every seed: the same sizes and gaps, in another order
    assert np.array_equal(np.sort(a.n), np.sort(c.n))
    # the gaps are one set in two orders: the windows end within a gap
    assert abs(a.t[-1] - c.t[-1]) <= np.diff(a.t).max()
    assert a.requests == round(mix["rate_per_s"] * 2.0)
    assert a.n.min() >= 1 and a.n.max() <= 64
    assert 12 < a.n.mean() < 17 and np.median(a.n) in (7, 8, 9)
    assert np.all(a.start + a.n <= mix["pool_items"])
    assert a.pool.dtype == np.float32 and a.pool.min() >= -1.0 and \
        a.pool.max() <= 1.0


def test_open_loop_rate_comes_from_the_cell_alone():
    mix = catalog.mix("serve")
    assert "rate_per_s" not in mix
    with pytest.raises(ValueError, match="cells/"):
        traffic.open_schedule(mix, 1, 1.0, 784)
    for c in catalog.benchmark()["workloads"]:
        if catalog.mix(c["traffic"])["loop"] == "open":
            assert catalog.mix(c["traffic"], c["name"])["rate_per_s"] > 0


class _SlowDeployment:
    """One request per step, each step 20 ms: the generator runs late
    behind it."""

    class _Router:
        def __init__(self):
            self.queue, self.active, self.finished = [], {}, []
            self.steps = 0

        def submit_app(self, app, req):
            self.queue.append(req)
            return req

    def __init__(self):
        self.router = self._Router()

    def step(self):
        import time

        from repro.serving.engine import ItemRequestState

        r = self.router
        time.sleep(0.02)
        req = r.queue.pop(0)
        st = ItemRequestState(req, 0, pos=len(req.items),
                              outputs=list(np.zeros((len(req.items), 2))))
        st.t_done = time.perf_counter()
        r.finished.append(st)
        r.steps += 1


def test_latency_runs_from_scheduled_arrival():
    sched = traffic.OpenSchedule(t=np.array([0.0, 0.005, 0.010]),
                                 start=np.zeros(3, int),
                                 n=np.ones(3, int),
                                 pool=np.zeros((4, 3), np.float32))
    win = loops.open_loop(_SlowDeployment(), "a", sched, 0.02, 1.0)
    assert win.finished == 3
    order = np.argsort(win.arrival_s)
    lat, late = win.latency_s[order], win.late_s
    # request 1 was due at 5 ms but submitted only after the first
    # 20 ms step: its latency counts that wait
    assert late[1] > 0.01
    assert lat[1] >= late[1] + 0.02 - 1e-3
    assert lat[2] >= 0.05 - 0.010 - 1e-3


def test_work_counts():
    assert workcount.flops([784, 200, 100, 10], 1) == 355_600
    assert workcount.flops([3072, 100, 10], 1) == 616_400
    assert workcount.bytes_moved([784, 200, 100, 10], 4096, 4) == \
        4096 * 784 * 4 + 177_800 * 4 + 4096 * 10 * 4
    assert workcount.bytes_moved([3072, 100, 10], 1, 1) == \
        3072 * 4 + 308_200 + 10 * 4
    t = workcount.min_seconds([3072, 100, 10], 4096, 1, 393e12, 819e9)
    assert t == pytest.approx(workcount.bytes_moved(
        [3072, 100, 10], 4096, 1) / 819e9)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "new-app.json").write_text(
        json.dumps({"app": "ocr", "dims": [2500, 60, 26]}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 10}))
    (tmp_path / "cells" / "new-app.burst.json").write_text(
        json.dumps({"rate_per_s": 20}))
    (tmp_path / "metrics" / "queue.depth_max.py").write_text(
        "def read(ctx):\n    return ctx['depth'] * 2\n")
    assert catalog.config("new-app", bench_dir=str(tmp_path))["dims"] == \
        [2500, 60, 26]
    assert catalog.mix("burst", bench_dir=str(tmp_path))["rate_per_s"] == 10
    assert catalog.mix("burst", "new-app.burst",
                       bench_dir=str(tmp_path))["rate_per_s"] == 20
    assert catalog.reader("queue.depth_max", str(tmp_path))(
        {"depth": 4}) == 8
    bench = {"workloads": [{"name": "new-app.burst"}],
             "per_layer": [{"name": "queue.depth_max"},
                           {"name": "other", "workloads": ["x.y"]}]}
    assert [m["name"] for m in catalog.metrics_for(
        bench, "new-app.burst", "per_layer")] == ["queue.depth_max"]


def test_every_metric_and_cell_has_its_files():
    bench = catalog.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(catalog.reader(m["name"]))
    for c in bench["workloads"]:
        cfg = catalog.config(c["config"])
        assert cfg["chips"] == c["chips"]
        tenant = catalog.tenant(cfg["tenant"])
        assert all(callable(getattr(tenant, f)) for f in TENANT_FUNCTIONS)
        assert catalog.mix(c["traffic"], c["name"])["loop"] in \
            ("open", "closed")
    assert catalog.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        catalog.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_sensor_cells_rehearse_at_their_own_size(cell):
    """The CPU rehearsal of a sensor cell is the cell as the chip runs
    it, so the fault tests above break the timed sizes."""
    c = catalog.cell(with_held(catalog.benchmark()), cell)
    cfg = catalog.config(c["config"])
    mix = catalog.mix(c["traffic"], cell)
    tenant = catalog.tenant(cfg["tenant"])
    assert cfg["tenant"] == "sensor"
    assert tenant.rehearsal(cfg, mix) == (cfg, mix)
