"""repro.lm — language-model tenants on the crossbar fabric.

:func:`compile_lm` maps a dense or MoE transformer's linears onto
programmed tile grids (same split→pack→place→route→program pipeline as
the sensor apps; attention/rotary/KV-cache glue stays host-graph), and
:class:`LMMember` serves the result as an ordinary ``deploy()`` tenant
— one decode step per lane through the same keyed scheduler, per-app
stats and Tables II–VI cost rows composing exactly like any sensor
app:

  from repro.configs import qwen1p5_0p5b
  from repro.deploy import AppSpec, deploy

  d = deploy(AppSpec("lm", qwen1p5_0p5b.reduced_serving(),
                     cache_len=64, lanes_per_chip=2))
  d.submit_tokens("lm", prompt, max_new_tokens=16)
  d.run_until_drained()
  print(d.generated_tokens("lm"))      # == dense serving.Engine exactly

Self-check:  PYTHONPATH=src python -m repro.lm --selftest
(2 simulated devices; asserts mapped == dense at rel ≤ 1e-6 on both
systems, exact token parity through a sensor+LM duo, and exact
``lm.tokens`` telemetry accounting).

Submodule imports are lazy (PEP 562) so ``python -m repro.lm`` can pin
``--xla_force_host_platform_device_count`` before jax initializes,
same as ``repro.deploy``.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "CompiledLM": "repro.lm.compile",
    "LM_LINEARS": "repro.lm.compile",
    "TransformerParams": "repro.lm.compile",
    "compile_lm": "repro.lm.compile",
    "DEFAULT_CACHE_LEN": "repro.lm.serving",
    "LMMember": "repro.lm.serving",
    "LMRequest": "repro.lm.serving",
    "lm_request": "repro.lm.serving",
    "tokens_from_state": "repro.lm.serving",
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
