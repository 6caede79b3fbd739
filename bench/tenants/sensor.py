"""The sensor tenant: one of the paper's streaming MLP apps on crossbar
cores, deployed through ``repro.deploy.deploy``.

A tenant owns everything in a cell that depends on what the deployment
runs; ``bench/run.py`` calls these functions, the work-count readers
call ``flops`` and ``min_call_seconds``, the CPU rehearsals in
``bench/tests`` call ``rehearsal``, and none names what lies behind
them:

  make_params(cfg, seed)                the weights, on the device
  deploy(cfg, params, chips)            the ``Deployment`` (timed as
                                        ``setup.deploy_s``)
  make_load(cfg, mix, seed, seconds)    the host traffic of the window
  warm(dep, cfg, load)                  every shape the window will run
  window(dep, cfg, mix, load, seconds, span, slice_)
                                        the measured ``loops.Window``;
                                        its ``items`` are sensor items
  check(cfg, params, load, win)         the named numbers compared with
                                        ``cfg["check"]["limits"]``, and
                                        a line for the log
  flops(cfg, items)                     the work of ``items`` items
  min_call_seconds(cfg, mix, chips, peaks)
                                        the least time of one device
                                        call of the cell
  rehearsal(cfg, mix)                   the (configuration, mix) that a
                                        CPU rehearsal of the cell runs
                                        at; the chip always runs the
                                        cell's own

The weights are ``bench/weights.py``'s, the traffic
``bench/traffic.py``'s, the loops ``bench/loops.py``'s, the reference
``bench/reference.py``'s and the work count ``bench/workcount.py``'s,
all from the configuration's published widths (``cfg["dims"]``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from bench import loops, reference, traffic, weights, workcount


@dataclasses.dataclass(frozen=True)
class Load:
    """The host traffic of one window: an open-loop schedule, or the
    closed loop's batches with the seeded calls it checks (``keep``).
    ``pool`` holds every item either can send, one row each."""
    pool: np.ndarray
    schedule: Optional[traffic.OpenSchedule] = None
    batches: Optional[np.ndarray] = None
    keep: frozenset = frozenset()


def make_params(cfg: dict, seed: int):
    return weights.make(cfg, seed)


def deploy(cfg: dict, params, chips: int):
    from repro.deploy import AppSpec, DeploymentSpec, deploy as deploy_app

    app = AppSpec(cfg["app"], cfg["app"], params=params,
                  system=cfg["system"],
                  geom=(cfg["core"]["rows"], cfg["core"]["cols"]),
                  weight_bits=cfg["weight_bits"],
                  lanes_per_chip=cfg["lanes_per_chip"])
    dep = deploy_app(DeploymentSpec(apps=(app,), n_chips=chips,
                                    use_kernel=cfg["use_kernel"]))
    dims = tuple(dep.chip(cfg["app"]).dims)
    if dims != tuple(cfg["dims"]):
        raise ValueError(f"deployed widths {dims} differ from the "
                         f"configuration's {tuple(cfg['dims'])}")
    return dep


def make_load(cfg: dict, mix: dict, seed: int, seconds: float) -> Load:
    d_in = cfg["dims"][0]
    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, seed, seconds, d_in)
        return Load(pool=sched.pool, schedule=sched)
    batches = traffic.closed_batches(mix, seed, d_in)
    keep = frozenset(int(k) for k in traffic.rng(seed, 4).integers(
        0, int(mix["checked_call_range"]), int(mix["checked_calls"])))
    return Load(pool=batches.reshape(-1, d_in), batches=batches, keep=keep)


def warm(dep, cfg: dict, load: Load) -> None:
    if load.schedule is not None:
        loops.warm_open(dep, cfg["app"], load.schedule, dep.router.slots)
    else:
        loops.warm_closed(dep, cfg["app"], load.batches)


def window(dep, cfg: dict, mix: dict, load: Load, seconds: float,
           span=loops.no_span, slice_=None) -> loops.Window:
    if load.schedule is not None:
        return loops.open_loop(dep, cfg["app"], load.schedule, seconds,
                               float(mix["drain_s"]), span, slice_)
    return loops.closed_loop(dep, cfg["app"], load.batches, seconds,
                             load.keep, span, slice_)


def answers(cfg: dict, win: loops.Window):
    """(pool rows, outputs) of every answer the window kept."""
    d_out = cfg["dims"][-1]
    if not win.answers:
        return np.zeros(0, np.int64), np.zeros((0, d_out), np.float32)
    rows = np.concatenate([r for r, _ in win.answers])
    ys = np.concatenate([np.asarray(y, np.float32).reshape(-1, d_out)
                         for _, y in win.answers])
    return rows, ys


def check(cfg: dict, params, load: Load, win: loops.Window):
    """``rel_err`` and ``rms_err`` of the kept answers against the dense
    float32 reference, rows near a threshold left out."""
    ref, near = reference.forward(cfg, params, load.pool)
    rows, ys = answers(cfg, win)
    note = (f"{len(rows)} answers compared, {int(near[rows].sum())} near "
            "a threshold and left out of the float comparison")
    return reference.errors(ys, ref[rows], near[rows]), note


def flops(cfg: dict, items: int) -> int:
    return workcount.flops(cfg["dims"], items)


def min_call_seconds(cfg: dict, mix: dict, chips: int, peaks: dict) -> float:
    """One closed-loop call's items on one chip, at the larger of the
    compute bound and the memory bound."""
    items = math.ceil(int(mix["items_per_call"]) / chips)
    return workcount.min_seconds(cfg["dims"], items, cfg["weight_bytes"],
                                 peaks[cfg["peak"]],
                                 peaks["hbm_bytes_per_s"])


def rehearsal(cfg: dict, mix: dict):
    """The cell's own size: the paper's apps fit a CPU test worker."""
    return cfg, mix
