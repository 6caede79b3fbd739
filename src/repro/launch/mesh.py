"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing one
CPU device.

Mesh axes:
  pod   — inter-pod data parallelism (parameters replicated across pods;
          the only cross-pod traffic is the gradient all-reduce)
  data  — intra-pod data parallel + FSDP (weights' d_model dim)
  model — tensor/expert/sequence parallel
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax


def make_auto_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axis types: the fleet and training
    code shards through ``shard_map`` and ``NamedSharding`` and does
    not type its values with explicit-axis shardings."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_fleet_mesh(n_chips: Optional[int] = None):
    """1-D device mesh for a served chip fleet: one simulated chip per
    device on a ``"chip"`` axis (data-parallel replica fan-out —
    ``repro.fleet.shard_chip`` shards the item batch over it, the
    programmed plan rides replicated)."""
    n = n_chips or len(jax.devices())
    if n > len(jax.devices()):
        raise ValueError(f"make_fleet_mesh: {n} chips requested but "
                         f"only {len(jax.devices())} devices visible "
                         f"(set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count "
                         f"before jax initializes to simulate more)")
    return make_auto_mesh((n,), ("chip",))


def make_chip_submesh(mesh, indices):
    """1-D ``"chip"`` mesh over a subset of ``mesh``'s devices — the
    heterogeneous-fleet building block: ``repro.deploy`` gives each
    chip *system* (memristor / digital) its own submesh of the one
    fleet, and each app's plan is placed on its system's submesh.

    ``indices`` index into the flat device order of ``mesh``. Single
    process only: a submesh is placed with plain ``device_put``, which
    needs every device addressable from this process.
    """
    import numpy as np

    flat = list(mesh.devices.flat)
    if not indices:
        raise ValueError("make_chip_submesh: at least one device index")
    bad = [i for i in indices if not 0 <= i < len(flat)]
    if bad:
        raise ValueError(f"make_chip_submesh: indices {bad} out of "
                         f"range for a {len(flat)}-device mesh")
    devs = [flat[i] for i in indices]
    return jax.sharding.Mesh(np.asarray(devs), ("chip",))


def make_distributed_fleet_mesh(chips_per_process: Optional[int] = None):
    """1-D ``"chip"`` mesh spanning every process of a
    ``jax.distributed``-initialized job (process-major device order, so
    each process's chips hold a contiguous row-block of a
    ``P("chip")``-sharded batch — the layout
    :meth:`repro.fleet.ShardedChip.stream_local` scatters into).

    Every process contributes the same number of chips
    (``chips_per_process``, default: all of its local devices): SPMD
    computations over the mesh need every rank to participate, and a
    rank with zero mesh devices could never join the collective. On a
    single process this degrades to :func:`make_fleet_mesh` semantics.
    """
    import numpy as np

    by_proc: Dict[int, list] = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    # derive the per-process count from the GLOBAL device list (the
    # same on every rank) — using this rank's local count would build
    # rank-divergent meshes on heterogeneous hosts, which surfaces as
    # a shape mismatch or hang in the first collective, not an error
    min_local = min(len(ds) for ds in by_proc.values())
    per = min_local if chips_per_process is None else chips_per_process
    if not 1 <= per <= min_local:
        counts = {p: len(ds) for p, ds in sorted(by_proc.items())}
        raise ValueError(
            f"make_distributed_fleet_mesh: {per} chips per process "
            f"requested but the smallest process has {min_local} "
            f"local devices (per-process device counts: {counts}); "
            f"every process must contribute the same number of chips")
    devs = [d for p in sorted(by_proc)
            for d in sorted(by_proc[p], key=lambda d: d.id)[:per]]
    return jax.sharding.Mesh(np.asarray(devs), ("chip",))


def mesh_spans_processes(mesh) -> bool:
    """True when the mesh's devices live in more than one process —
    the signal that host scatter/gather must go through the
    process-local path instead of plain device_put."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def make_debug_mesh(n_devices: Optional[int] = None, model: int = 2):
    """Small mesh over however many (host) devices exist — for tests."""
    n = n_devices or len(jax.devices())
    model = math.gcd(model, n)
    return make_auto_mesh((n // model, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_degree(mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    return sizes.get("data", 1) * sizes.get("pod", 1)


def tp_degree(mesh) -> int:
    return mesh_axis_sizes(mesh).get("model", 1)
