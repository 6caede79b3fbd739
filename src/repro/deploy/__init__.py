"""repro.deploy — declarative multi-app deployment over one fabric.

The paper's processor serves five sensor applications from one
core/fabric design (Tables II–VI); this package is that multi-tenancy
as an API. Declare WHAT runs — apps, systems, SLOs, lane budgets, one
fabric topology — and ``deploy`` compiles, places and routes every app
(``compile_chip`` → ``ShardedChip`` → :class:`MultiAppRouter`, the one
router):

  from repro.deploy import AppSpec, DeploymentSpec, deploy

  d = deploy(DeploymentSpec(
      apps=(AppSpec("deep", "deep", system="1t1m"),
            AppSpec("ocr", "ocr", system="1t1m", lanes_per_chip=2)),
      n_chips=4))
  y = d.stream("deep", x)              # == legacy shard_chip path, rel 0.0
  d.submit("ocr", items); d.run_until_drained()
  d.serve({"deep": src_a, "ocr": src_b})   # sensor-fed closed loop
  print(d.stats())                     # per-app rows + exact fleet roll-up
  print(d.report())                    # multi-app Tables II–VI composition
  d.reprogram("deep", new_params)      # live weight swap, NO recompile
  d.close()

Self-check:  PYTHONPATH=src python -m repro.deploy --selftest
(2 simulated devices, 2 co-resident apps; asserts the per-app stats
roll-up is exact and the single-app stream matches
``compile_chip`` → ``shard_chip`` at rel 0.0).

Submodule imports are lazy (PEP 562) so ``python -m repro.deploy`` can
pin ``--xla_force_host_platform_device_count`` before jax initializes,
same as ``repro.fleet``.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "AppSpec": "repro.deploy.spec",
    "DeploymentSpec": "repro.deploy.spec",
    "single_app": "repro.deploy.spec",
    "Deployment": "repro.deploy.deployment",
    "deploy": "repro.deploy.deployment",
    "MultiAppRouter": "repro.deploy.router",
    "DistributedMultiAppRouter": "repro.deploy.router",
    "DeploymentStats": "repro.deploy.router",
    "DeploymentReport": "repro.deploy.report",
    "deployment_report": "repro.deploy.report",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
