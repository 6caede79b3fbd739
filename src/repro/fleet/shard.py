"""Sharding one compiled chip across a device mesh — the fleet fabric.

The paper scales a single streaming multicore chip; the fleet scales
the *chip*: ``shard_chip`` places one full copy of a
:class:`repro.chip.CompiledChip`'s programmed plan on every device of a
1-D ``"chip"`` mesh and shards the item batch across them
(data-parallel replica fan-out — the §V.C replication argument lifted
from cores-within-a-chip to chips-within-a-fleet). The plan pytree is
already jit-able static-programmed state, so the per-device body is
exactly ``stream_pipeline`` — the same arithmetic the single chip runs
— and the sharded stream matches ``CompiledChip.stream`` bit-for-bit
(rel 0.0): batch rows are independent, so splitting them across devices
cannot reassociate any reduction.

The mesh may span PROCESSES: build it with
:func:`repro.launch.mesh.make_distributed_fleet_mesh` under an
initialized ``jax.distributed`` runtime and the same ShardedChip works
multi-host, with two changes this module owns:

  * the plan is replicated onto every *local* mesh device and assembled
    into one global replicated array
    (``jax.make_array_from_single_device_arrays``) — every process
    programs its own chips from its own (identical, deterministic)
    compile, so programming the fleet moves no bytes between hosts;
  * scatter/gather goes through :meth:`ShardedChip.stream_local`: each
    process contributes only ITS rows
    (``jax.make_array_from_process_local_data``) and reads back only
    its devices' output shards. The global-batch ``stream`` /
    ``stream_host`` verbs refuse on a multi-process mesh — a host
    cannot address the other hosts' devices, and pretending otherwise
    would mean shipping every batch through host 0.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.chip.compile import (CompiledChip, reprogram_chip,
                                stream_pipeline, validate_stream_rate)
from repro.launch.mesh import make_fleet_mesh, mesh_spans_processes
from repro.obs.core import current as _obs_current, span as _obs_span


def replicate_to_mesh(tree, mesh: jax.sharding.Mesh):
    """Fully replicate a pytree onto every device of ``mesh``, multi-
    process safe.

    Single-process this is plain ``device_put`` with a replicated
    NamedSharding. Across processes ``device_put`` cannot reach
    non-addressable devices, so each process stages the (identical)
    host value onto its own mesh devices and the per-device buffers are
    assembled into one global replicated array — no cross-host
    transfer, which is what makes fleet programming O(local devices)
    instead of O(cluster).
    """
    sharding = NamedSharding(mesh, P())
    if not mesh_spans_processes(mesh):
        return jax.device_put(tree, sharding)
    me = jax.process_index()
    local = [d for d in mesh.devices.flat if d.process_index == me]

    def leaf(x):
        x = np.asarray(x)
        shards = [jax.device_put(x, d) for d in local]
        return jax.make_array_from_single_device_arrays(
            x.shape, sharding, shards)

    return jax.tree_util.tree_map(leaf, tree)


def _stage(x, parts: int):
    """The host half of a scatter: x (..., d_in) as float32 rows,
    zero-padded to a multiple of ``parts``. Returns (padded rows, lead
    shape, item count, pad row count)."""
    xf = np.asarray(x, np.float32)
    lead = xf.shape[:-1]
    xf = xf.reshape(-1, xf.shape[-1])
    n = xf.shape[0]
    pad = math.ceil(max(n, 1) / parts) * parts - n
    if pad:
        xf = np.pad(xf, ((0, pad), (0, 0)))
    return xf, lead, n, pad


@functools.partial(jax.jit, static_argnums=1)
def _first_rows(y: jax.Array, n: int) -> jax.Array:
    """The first ``n`` rows of a device array, cut on its devices: one
    compiled program per row count, several times cheaper to dispatch
    than the eager slice of a sharded array."""
    return y[:n]


def _read_local_shards(out: jax.Array) -> np.ndarray:
    """This process's output shards, read back in row order."""
    shards = sorted(out.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


@dataclasses.dataclass
class ShardedChip:
    """One compiled chip served as ``n_chips`` identical fleet members.

    ``stream`` deals the batch across the mesh's ``"chip"`` axis (padded
    to a multiple of the fleet size where it must be), runs the mapped
    dataflow on every device, and returns the outputs as one array —
    semantically identical to the single chip, ``n_chips``× the lanes.
    ``report`` mirrors the CompiledChip verb at fleet scale. On a
    multi-process mesh use ``stream_local``; see the module docstring.
    Routed serving is :func:`repro.deploy.deploy`'s router, which
    calls ``stream_host`` (``stream_local`` when distributed).

    ``items_per_second`` is an optional FLEET-level target rate: the
    compile already validated the chip's own target against its routed
    TDM schedule, but a fleet target must be re-validated against
    ``replication × n_chips`` fabric copies — capacity multiplies with
    the fleet, and silently assuming so is exactly the bug this check
    closes. Infeasible targets warn (:class:`ChipRateWarning`) or, with
    ``strict_rate=True``, raise. When the fleet target IS the rate the
    compile already validated (``chip.rate_validated``), the check is
    skipped: fleet capacity is chip capacity × n_chips, so the
    compile's verdict (pass or one warning) already covers it —
    re-checking would only duplicate the diagnostic.
    """
    chip: CompiledChip
    mesh: jax.sharding.Mesh
    axis: str = "chip"
    items_per_second: float = 0.0
    strict_rate: bool = False

    def __post_init__(self):
        if self.chip.plan is None:
            raise ValueError(
                "shard_chip needs a streamable chip (compiled with "
                "weights); this one is analytic-only")
        if not (self.chip.rate_validated and
                self.items_per_second == self.chip.items_per_second):
            validate_stream_rate(
                self.items_per_second,
                self.chip.replication * self.mesh.devices.size,
                self.chip.route, self.strict_rate,
                context="shard_chip",
                fabric=(f"fleet replica(s) ({self.mesh.devices.size} "
                        f"chip(s) x {self.chip.replication} "
                        f"replica(s))"),
                remedy=("Add chips to the fleet, use a larger core "
                        "geometry, or lower the fleet target rate."),
                # point the warning at shard_chip's caller: stacklevel
                # counts validate_stream_rate(1) → __post_init__(2) →
                # dataclass __init__(3) → shard_chip(4) → user(5)
                stacklevel=5,
                chip_replicas=self.chip.replication)
        self._fns: Dict[tuple, callable] = {}
        # program the fleet ONCE: replicate the tile image onto every
        # mesh device at shard time (§III.D program-once, fleet-level).
        # Without this, every stream call would re-transfer the plan
        # from host/device-0 to the mesh — per-step programming traffic
        # that dwarfs the item traffic.
        self._plan = replicate_to_mesh(self.chip.plan, self.mesh)

    # ------------------------------------------------------------ #
    @property
    def n_chips(self) -> int:
        return self.mesh.devices.size

    @property
    def is_distributed(self) -> bool:
        """True when the fleet's mesh spans jax processes."""
        return mesh_spans_processes(self.mesh)

    @property
    def local_chips(self):
        """This process's mesh devices, in mesh (row-block) order."""
        me = jax.process_index()
        return [d for d in self.mesh.devices.flat
                if d.process_index == me]

    @property
    def n_local_chips(self) -> int:
        return len(self.local_chips)

    @property
    def d_in(self) -> int:
        return self.chip.dims[0]

    @property
    def d_out(self) -> int:
        return self.chip.dims[-1]

    @property
    def total_cores(self) -> int:
        return self.chip.total_cores * self.n_chips

    @property
    def has_drift(self) -> bool:
        return self.chip.has_drift

    def _age(self) -> Optional[jax.Array]:
        """The fleet's drift age, as a traced scalar (None when the
        chip's devices do not drift). Every member replica shares the
        source chip's clock: the fleet members are copies of the SAME
        programmed (and thus equally aged) physical image."""
        if not self.has_drift:
            return None
        return jnp.asarray(float(self.chip.items_streamed), jnp.float32)

    # ------------------------------------------------------------ #
    def _fn(self, use_kernel: bool, drift: bool = False):
        fn = self._fns.get((use_kernel, drift))
        if fn is None:
            rep = self.chip.replication

            if drift:
                def per_chip(plan, xs, age):
                    return stream_pipeline(plan, xs,
                                           use_kernel=use_kernel,
                                           replication=rep, age=age)

                in_specs = (P(), P(self.axis), P())
            else:
                def per_chip(plan, xs):
                    return stream_pipeline(plan, xs,
                                           use_kernel=use_kernel,
                                           replication=rep)

                in_specs = (P(), P(self.axis))
            # each chip runs its replica alone, with no collective, so
            # there is no cross-chip typing to check; Pallas kernels in
            # the body could not state it anyway
            fn = jax.jit(jax.shard_map(per_chip, mesh=self.mesh,
                                       in_specs=in_specs,
                                       out_specs=P(self.axis),
                                       check_vma=False))
            self._fns[(use_kernel, drift)] = fn
        return fn

    def _refuse_distributed(self) -> None:
        if self.is_distributed:
            raise ValueError(
                "stream/stream_host need every fleet device to be "
                "addressable from this process, but the mesh spans "
                f"{len({d.process_index for d in self.mesh.devices.flat})} "
                "processes. Use stream_local(x_local): every process "
                "passes its own rows and reads back its own outputs.")

    def _put(self, xf: np.ndarray) -> jax.Array:
        """Staged host rows straight into the fleet layout, one slice
        per chip."""
        return jax.device_put(xf, NamedSharding(self.mesh, P(self.axis)))

    def _member_call(self, name: str, x, parts: int, place, read_back,
                     use_kernel: bool, **args):
        """One member call: stage ``x`` into float32 rows padded to
        ``parts``, ``place`` them on the mesh, run the stream program,
        then ``read_back`` its outputs to the host and drop the pad
        rows, or, with ``read_back=None``, drop them on the device and
        return the program's output there once it is ready: the
        placement may alias the caller's host buffer (the CPU client
        does, for aligned float32 rows), so the program must have read
        it before the call returns. Under drift the program evaluates
        at the fleet's age and the clock then advances by this call's
        rows (on a multi-process mesh each process advances its own
        copy by its OWN rows; SPMD symmetry, equal local rows per call,
        keeps the copies in agreement).

        With telemetry on, the call is the ``name`` span tiled by
        ``fleet.stage`` (cast, reshape, host pad), ``fleet.h2d`` (the
        placement), ``fleet.program`` (dispatch to done; without a
        read-back, pad rows dropped) and, for a read-back,
        ``fleet.d2h`` (read-back, pad rows dropped). The placement and
        the program are waited on inside their own spans: both are
        asynchronous, and their time would otherwise land in the span
        that waits next. The counters ``fleet.h2d_bytes`` and
        ``fleet.d2h_bytes`` (pad rows included) and ``fleet.pad_rows``
        count what moved. Off, nothing is counted and only a call
        without a read-back waits, on its program."""
        tel = _obs_current()
        with _obs_span(name, cat="fleet", **args) as sp:
            with _obs_span("fleet.stage", cat="fleet"):
                xf, lead, rows, pad = _stage(x, parts)
            with _obs_span("fleet.h2d", cat="fleet"):
                xs = place(xf)
                if tel.active:
                    xs.block_until_ready()
            with _obs_span("fleet.program", cat="fleet"):
                age = self._age()
                if age is None:
                    out = self._fn(use_kernel)(self._plan, xs)
                else:
                    out = self._fn(use_kernel, True)(self._plan, xs, age)
                    self.chip.advance_age(rows)
                if read_back is None and pad:
                    out = _first_rows(out, rows)
                if tel.active or read_back is None:
                    out.block_until_ready()
            y = out
            if read_back is not None:
                with _obs_span("fleet.d2h", cat="fleet"):
                    host = read_back(out)
                    y = host[:rows]
            if tel.active:
                sp.args["rows"] = int(rows)
                m = tel.metrics
                m.counter("fleet.h2d_bytes").inc(int(xf.nbytes))
                m.counter("fleet.pad_rows").inc(int(pad))
                if read_back is not None:
                    m.counter("fleet.d2h_bytes").inc(int(host.nbytes))
        if len(lead) != 1:
            y = y.reshape(*lead, y.shape[-1])
        return y

    def stream_host(self, x, *, use_kernel: bool = False) -> np.ndarray:
        """Host-to-host fleet stream: x (..., d_in) → (..., d_out) as a
        float32 numpy array — the serving hot path.

        The batch is staged host-side, ``device_put`` straight into the
        fleet layout (one slice per chip), and the result gathered back
        to host before the pad rows are dropped. Handing the jit a
        device-committed array would make XLA reshard it with
        chip-to-chip copies every step, and slicing the still-sharded
        output would dispatch a second cross-chip computation — each
        measured in the ms/step range on the CPU client vs ~0.1 ms for
        the host scatter/gather, i.e. the difference between the fleet
        scaling and not. Traced, the call is the ``fleet.stream_host``
        span and its four children (:meth:`_member_call`).
        """
        self._refuse_distributed()
        return self._member_call("fleet.stream_host", x, self.n_chips,
                                 self._put, np.asarray, use_kernel,
                                 chips=self.n_chips)

    def stream_local(self, x, *, use_kernel: bool = False) -> np.ndarray:
        """Process-local scatter/gather: x (..., d_in) is THIS
        process's rows; returns this process's (..., d_out) outputs.

        Every participating process must call this together with the
        same number of rows (SPMD — the call assembles one global array
        via ``jax.make_array_from_process_local_data`` and enters one
        global computation; mismatched local shapes make the ranks
        disagree on the global shape and fail). The rows land on this
        process's own mesh devices and only their output shards are
        read back, so no item bytes ever cross hosts — the fleet-scale
        analogue of the paper's sensors feeding each chip's TSV
        interface directly.

        Single-process it is equivalent to :meth:`stream_host` (one
        process owns all rows), which keeps the tier-1 suite able to
        pin its semantics without spawning a cluster. Traced, the call
        is the ``fleet.stream_local`` span and the same four children.
        """
        sharding = NamedSharding(self.mesh, P(self.axis))
        n_local = self.n_local_chips
        return self._member_call(
            "fleet.stream_local", x, n_local,
            lambda xf: jax.make_array_from_process_local_data(sharding, xf),
            _read_local_shards, use_kernel, local_chips=n_local)

    def stream(self, x: jax.Array, *,
               use_kernel: bool = False) -> jax.Array:
        """Stream a batch through the fleet: x (..., d_in) → (..., d_out)
        as a ``jax.Array``, rows dealt across chips, each chip running
        the mapped dataflow on its shard.

        The program's output is returned where it is, on the member's
        own devices, with no read-back and no re-upload. The call
        returns when the program is done, so ``x`` may be overwritten
        as soon as it returns. When the rows divide across the chips
        (always, on one chip) the result stays sharded over the mesh;
        ragged rows are cut to size on the same chips, in a layout the
        compiler picks (replicated for an odd row count). The result is
        committed to those devices, so combining it with the output of a member
        on other devices needs a ``jax.device_put`` first. Traced, the
        call is the ``fleet.stream`` span tiled by ``fleet.stage``,
        ``fleet.h2d`` and ``fleet.program``. Host-side consumers like
        the router call :meth:`stream_host` directly."""
        self._refuse_distributed()
        dtype = jax.dtypes.canonicalize_dtype(
            x.dtype if hasattr(x, "dtype") else jnp.float32)
        out = self._member_call("fleet.stream", x, self.n_chips,
                                self._put, None, use_kernel,
                                chips=self.n_chips)
        return out if out.dtype == dtype else out.astype(dtype)

    def __call__(self, x: jax.Array, **kw) -> jax.Array:
        return self.stream(x, **kw)

    def resize(self, n_chips: Optional[int] = None, *,
               mesh: Optional[jax.sharding.Mesh] = None) -> None:
        """Elastic remesh: re-place the SAME programmed plan on a new
        ``"chip"`` mesh (grown, shrunk, or rebuilt from surviving
        devices after a failure) — ZERO compile passes, because the
        program-once plan is mesh-agnostic: only the replication
        (``replicate_to_mesh``) and the cached per-mesh jitted
        dispatchers change. The jit cache is dropped (the mesh is part
        of the shard_map closure), so the first step on the new mesh
        re-traces the same per-chip body — an XLA re-trace, not a chip
        compile (``compile_count()`` is the pin).

        Default mesh is :func:`make_fleet_mesh` over the process's
        visible devices; pass an explicit ``mesh`` to rebuild from a
        survivor subset (:func:`repro.fleet.ha.local_fleet_mesh`).
        The fleet rate target is re-validated against the new capacity
        — shrinking below the declared ``items_per_second`` warns (or
        raises under ``strict_rate``), which is exactly the degraded-
        mode SLO signal.
        """
        if mesh is None:
            mesh = make_fleet_mesh(n_chips)
        elif self.axis not in mesh.axis_names:
            raise ValueError(
                f"resize: mesh has no {self.axis!r} axis "
                f"(axes: {mesh.axis_names})")
        self.mesh = mesh
        self._fns = {}
        self._plan = replicate_to_mesh(self.chip.plan, self.mesh)
        validate_stream_rate(
            self.items_per_second,
            self.chip.replication * self.mesh.devices.size,
            self.chip.route, self.strict_rate,
            context="ShardedChip.resize",
            fabric=(f"fleet replica(s) ({self.mesh.devices.size} "
                    f"chip(s) x {self.chip.replication} replica(s))"),
            remedy=("Add chips to the fleet, use a larger core "
                    "geometry, or lower the fleet target rate."),
            stacklevel=3,
            chip_replicas=self.chip.replication)

    def reprogram(self, params, **kw) -> None:
        """Live weight swap: re-encode ``params`` into tile state for
        the SAME compiled fabric and re-place the plan on every mesh
        device — map/route never run (:func:`repro.chip.reprogram_chip`)
        and the jitted per-chip step stays warm (the new plan is the
        same pytree structure, so no retrace). Call between engine
        steps; in-flight lanes see the new weights on their next item,
        exactly like re-flashing a crossbar mid-stream."""
        self.chip = reprogram_chip(self.chip, params, **kw)
        self._plan = replicate_to_mesh(self.chip.plan, self.mesh)

    def report(self, served=None):
        """Fleet-level roll-up of the per-chip Tables II–VI report,
        with this member's served :class:`RouterStats` row folded in
        when given."""
        from repro.fleet.report import fleet_report
        return fleet_report(self, served)


def shard_chip(chip: CompiledChip, n_chips: Optional[int] = None, *,
               mesh: Optional[jax.sharding.Mesh] = None,
               axis: str = "chip",
               items_per_second: float = 0.0,
               strict_rate: bool = False) -> ShardedChip:
    """Fan one compiled chip out over ``n_chips`` devices (default: all
    visible). Pass an existing 1-D ``mesh`` to reuse a launcher mesh
    instead of building a fresh one (including a
    ``make_distributed_fleet_mesh`` spanning processes).

    ``items_per_second`` declares the rate target for the WHOLE fleet;
    it is validated against ``replication × n_chips`` copies of the
    chip's routed TDM fabric (warn / ``strict_rate=True`` raise) — the
    single-chip compile cannot have vouched for it.
    """
    if mesh is None:
        mesh = make_fleet_mesh(n_chips)
    elif axis not in mesh.axis_names:
        raise ValueError(f"shard_chip: mesh has no {axis!r} axis "
                         f"(axes: {mesh.axis_names})")
    return ShardedChip(chip, mesh, axis,
                       items_per_second=items_per_second,
                       strict_rate=strict_rate)
