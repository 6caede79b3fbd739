"""Elastic scaling: resume a job on a different device count/topology.

Because (a) checkpoints store unsharded global arrays and (b) a batch
is a pure function of (seed, step), elasticity reduces to:

  1. build the *new* mesh from whatever devices exist now,
  2. re-derive shardings from the same logical rules on that mesh,
  3. ``restore(..., shardings=new)`` — reshard-on-load,
  4. continue from the manifest's step; the data pipeline yields the
     identical global batch stream.

``remesh()`` below packages 1–3. ``tests/test_elastic.py`` proves the
invariant end-to-end in one process by simulating shrink (8→4 host
devices) and checking the loss trajectory is unchanged.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax

from repro.launch.mesh import make_auto_mesh
from repro.launch.rules import make_rules
from repro.sharding import axis_rules
from repro.train import checkpoint as ckpt_lib


def best_mesh_for(n_devices: int, model_parallel: int = 1):
    """Largest (data, model) mesh for the surviving device count."""
    model = math.gcd(model_parallel, n_devices)
    return make_auto_mesh((n_devices // model, model),
                          ("data", "model"))


def remesh(ckpt_dir: str, step: Optional[int], cfg, *,
           mesh=None, mode: str = "train",
           global_batch: int = 8) -> Tuple[Any, Any, Any, int]:
    """Restore (params, opt_state) against a fresh mesh; returns
    (params, opt_state, mesh, step)."""
    from repro.launch import specs as specs_lib
    from repro.optim.adamw import AdamW, constant_schedule

    if step is None:
        step = ckpt_lib.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    mesh = mesh or best_mesh_for(len(jax.devices()))
    rules = make_rules(cfg, mesh, mode, global_batch=global_batch)
    with axis_rules(mesh, rules):
        psh = specs_lib.param_shardings(cfg, mesh)
        pshapes = specs_lib.param_shapes(cfg)
        opt = AdamW(lr=constant_schedule(1e-3))
        oshapes = specs_lib.opt_shapes(cfg, opt, pshapes)
        osh = specs_lib.opt_shardings(psh, mesh)
    (params, opt_state), manifest = ckpt_lib.restore(
        ckpt_dir, step, (pshapes, oshapes), shardings=(psh, osh))
    return params, opt_state, mesh, manifest["step"]
