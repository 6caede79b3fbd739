"""repro.fleet — multi-chip streaming fabric.

One :func:`repro.chip.compile_chip` result, placed as a fleet:

  fleet = shard_chip(chip, n_chips)        # one chip copy per device
  y = fleet.stream(x)                      # == chip.stream(x), rel 0.0
  y = fleet.stream_host(x)                 # numpy in, numpy out
  print(fleet.report())                    # Tables II–VI, x n_chips

Routed serving over fleet members is :func:`repro.deploy.deploy` →
:class:`repro.deploy.MultiAppRouter`, fed by this package's bounded
sensor sources (:class:`StreamSource`).

The multi-process fabric has a self-check: ``python -m repro.fleet
--distributed-selftest`` self-spawns N localhost ``jax.distributed``
ranks (gloo collectives), checks ``ShardedChip.stream_local`` ==
single-chip at rel 0.0, drives the lockstep
``DistributedMultiAppRouter`` off per-host ``StreamSource.for_host``
feeders, and rolls router stats up across hosts. Fault tolerance has a
second: ``python -m repro.fleet --chaos-selftest`` kills a worker
mid-serve and asserts the survivors degrade, absorb the dead rank's
feed, and account for every admitted item exactly once (see
:mod:`repro.fleet.ha`).

Submodule imports are lazy (PEP 562) so importing ``repro.fleet`` —
and in particular ``python -m repro.fleet`` booting this package —
never initializes jax; the CLI can still pin
``--xla_force_host_platform_device_count`` first.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "ShardedChip": "repro.fleet.shard",
    "shard_chip": "repro.fleet.shard",
    "replicate_to_mesh": "repro.fleet.shard",
    "BoundedQueue": "repro.fleet.source",
    "StreamSource": "repro.fleet.source",
    "FleetReport": "repro.fleet.report",
    "fleet_report": "repro.fleet.report",
    "HAConfig": "repro.fleet.ha",
    "HeartbeatBoard": "repro.fleet.ha",
    "FailureDetector": "repro.fleet.ha",
    "StepGuard": "repro.fleet.ha",
    "MembershipChange": "repro.fleet.ha",
    "HAFleetServer": "repro.fleet.ha",
    "degrade_to_local": "repro.fleet.ha",
    "local_fleet_mesh": "repro.fleet.ha",
    "source_snapshot": "repro.fleet.ha",
    "replay_requests": "repro.fleet.ha",
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
