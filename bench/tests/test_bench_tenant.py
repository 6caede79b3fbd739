"""The seam between the harness and a tenant: a tenant kind that the
harness has never seen, written with its configuration and mix only
into a scratch directory, runs through ``run.run_cell`` end to end, at
the CPU rehearsal size it names where its own is too large for a test
worker; a configuration with no tenant, or an unknown one, is refused
by name."""
import json

import jax
import pytest

from bench import catalog, run
from bench.tests.test_bench_harness import (CONTRACT_KEYS,
                                            TENANT_FUNCTIONS, rehearse)

STUB = '''"""Scales its items by the configuration's factor on the device."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import loops

ALTER = {alter}


class Deployment:
    def __init__(self, scale):
        self.run = jax.jit(lambda x: x * scale)

    def report(self):
        return "stub"

    def close(self):
        pass


def make_params(cfg, seed):
    return jnp.float32(cfg["scale"])


def deploy(cfg, params, chips):
    return Deployment(params)


def make_load(cfg, mix, seed, seconds):
    if cfg["width"] > 64:
        raise MemoryError("too wide for a CPU test worker")
    shape = (mix["batches"], mix["items_per_call"], cfg["width"])
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def warm(dep, cfg, load):
    jax.block_until_ready(dep.run(load[0]))


def window(dep, cfg, mix, load, seconds, span, slice_):
    t0 = time.perf_counter()
    answers = []
    while not answers or time.perf_counter() - t0 < seconds:
        b = len(answers) % len(load)
        answers.append((b, np.array(jax.block_until_ready(
            dep.run(load[b])))))
    if ALTER:
        answers[0][1][0, 0] += 1.0
    n = len(answers)
    return loops.Window(window_s=time.perf_counter() - t0, attempted=n,
                        finished=n, items=n * load.shape[1], calls=n,
                        answers=answers)


def check(cfg, params, load, win):
    gap = max(float(np.max(np.abs(y - load[b] * cfg["scale"])))
              for b, y in win.answers)
    return {{"max_gap": gap}}, f"{{len(win.answers)}} calls compared"


def flops(cfg, items):
    return items * cfg["width"]


def min_call_seconds(cfg, mix, chips, peaks):
    return mix["items_per_call"] * cfg["width"] * 8 / peaks[
        "hbm_bytes_per_s"]


def rehearsal(cfg, mix):
    return dict(cfg, **cfg["rehearsal"]), mix
'''


def _write_stub(tmp_path, alter):
    for folder in ("configs", "traffic", "tenants"):
        (tmp_path / folder).mkdir()
    (tmp_path / "tenants" / "stub.py").write_text(STUB.format(alter=alter))
    (tmp_path / "configs" / "stub-app.json").write_text(json.dumps(
        {"tenant": "stub", "chips": 1, "scale": 2.0, "width": 1 << 40,
         "rehearsal": {"width": 3},
         "check": {"limits": {"max_gap": 1e-6}}}))
    (tmp_path / "traffic" / "calls.json").write_text(json.dumps(
        {"loop": "closed", "batches": 2, "items_per_call": 4}))
    cell = {"name": "stub-app.calls", "config": "stub-app",
            "traffic": "calls", "chips": 1, "why": "a stub"}
    bench = {"workloads": [cell], "per_layer": [],
             "end_to_end": [{"name": "items_per_s", "unit": "items/s"},
                            {"name": "setup_s", "unit": "s"}]}
    cfg = catalog.config("stub-app", bench_dir=str(tmp_path))
    mix = catalog.mix("calls", cell["name"], bench_dir=str(tmp_path))
    return bench, cell, cfg, mix, catalog.tenant(cfg["tenant"],
                                                 str(tmp_path))


@pytest.mark.parametrize("alter", [False, True])
def test_a_new_tenant_kind_runs_through_the_harness(tmp_path, alter):
    bench, cell, cfg, mix, tenant = _write_stub(tmp_path, alter)
    assert all(callable(getattr(tenant, f)) for f in TENANT_FUNCTIONS)
    # the CPU rehearsal that runs every cell of BENCHMARK.json
    res = rehearse(run.run_cell)(bench, cell, cfg, mix, tenant, 2**40 + 3,
                                 0.2, False, jax.devices())
    assert set(res) == CONTRACT_KEYS and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"items_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    gap = res["checks"]["max_gap"]
    assert res["correct"] is (not alter)
    assert (gap["value"] > gap["limit"]) is alter


def test_a_tenant_too_large_for_the_cpu_needs_its_rehearsal(tmp_path):
    bench, cell, cfg, mix, tenant = _write_stub(tmp_path, False)
    with pytest.raises(MemoryError):
        run.run_cell(bench, cell, cfg, mix, tenant, 2**40 + 3, 0.2, False,
                     jax.devices())


@pytest.mark.parametrize("name", [None, "no-such-tenant"])
def test_a_configuration_names_a_known_tenant(monkeypatch, name):
    real_config = catalog.config

    def config(*a, **kw):
        cfg = dict(real_config(*a, **kw))
        del cfg["tenant"]
        if name is not None:
            cfg["tenant"] = name
        return cfg

    monkeypatch.setattr(catalog, "config", config)
    with pytest.raises(KeyError, match=r"known: .*sensor"):
        run.main(["--workload", "object-sram.bulk", "--seed", "1",
                  "--seconds", "1"])
