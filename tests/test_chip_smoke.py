"""``chip_smoke.py`` rehearsed on the CPU at small sizes.

The script itself refuses to run without a TPU; its phases are plain
functions, so the tests drive them here with a small batch to keep the
script's paths, arguments and checks from rotting between chip runs.
On the CPU the Pallas kernels run in interpret mode, so the one check
that must fail here is the one asserting a Mosaic lowering.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    mod = _load()
    # near-threshold rows are ~1% of a batch; 256 keeps the share bound
    # meaningful
    monkeypatch.setattr(mod, "BATCH", 256)
    monkeypatch.setattr(mod, "SERVE_REQUESTS", 6)
    return mod


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SMOKE], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_one_chip_phases_on_cpu(smoke):
    run = smoke.Run()
    smoke.one_chip(run)
    assert run.failed == [f"{app} kernel path lowers to Mosaic"
                          for app, _ in smoke.SENSOR_APPS]


def test_chip_smoke_reference_catches_a_wrong_stream(smoke):
    """The comparison is not vacuous: one output column off by 1e-3 of
    the output range fails it."""
    import jax
    import numpy as np

    from repro.deploy import AppSpec, deploy

    dep = deploy(AppSpec("deep", "deep"), n_chips=1)
    x = smoke.sensor_batch(jax.random.PRNGKey(0), smoke.BATCH, 784)
    plan = dep.chip("deep").plan
    ref, pre = smoke.reference(plan, x)
    y = np.asarray(dep.stream("deep", x))
    assert smoke.compare(y, ref, pre, plan)["ok"]
    bad = y.copy()
    bad[:, 3] += 1e-3 * np.abs(np.asarray(ref)).max()
    assert not smoke.compare(bad, ref, pre, plan)["ok"]
    dep.close()


FLEET_SCRIPT = f"""
import importlib.util, json
spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.BATCH = 256
mod.SERVE_REQUESTS = 6
run = mod.Run()
mod.fleet(run, 4)
print(json.dumps({{"failed": run.failed}}))
"""


def test_chip_smoke_fleet_phase_on_simulated_devices(sim_subprocess):
    assert sim_subprocess(FLEET_SCRIPT, n_devices=4) == {"failed": []}
