"""compile_chip: the unified compile → program → stream entry point.

The paper's pitch is a *system*: network topologies are compiled onto
fixed-geometry cores (§IV.C), the resulting flows are statically routed
over the 2-D mesh (§II.B), every mapped core is programmed once
(§III.D), and the programmed chip then streams items at a fixed rate.
``compile_chip`` runs that whole pipeline and returns a
:class:`CompiledChip` with two verbs:

  chip.stream(x)   — execute the *mapped* dataflow functionally:
                     stage-ordered group evaluation, per-row-chunk
                     sub-neuron partials, programmed combiner neurons
                     (Fig. 11), replica fan-out — through the fused
                     kernels / batched tile-grid einsum.
  chip.report()    — the unified area/power/throughput accounting the
                     Tables II–VI benchmarks previously assembled by
                     hand from mapping + routing + costmodel.

Serving goes through ``repro.deploy.deploy``, which places the chip on
a ``"chip"`` mesh and routes requests to it through the one router.

A CompiledChip is a jit-able pytree: the programmed conductance tiles,
fold scales and biases are array leaves; geometry, placement, stage
schedule and the mapping/routing reports are static aux data. Passing a
chip through ``jax.jit`` (or calling ``chip.stream`` repeatedly) never
re-programs tile state — the §III.D program-once economics are
structural, not a calling convention.

Functional tile layout vs the packer's row balancing: both split a
layer with ``fan_in > geom.rows`` into ``ceil(fan_in / geom.rows)``
row chunks (the Fig. 11 sub-neuron level). The packer balances rows
across chunks so link streaming time equalizes (7 chunks of 112 for
784 inputs); the functional image uses uniform ``geom.rows`` chunks so
the programmed tiles are bit-identical to ``program_layer``'s — the
same chunk *count* into the same cores, so placement, routing and the
cost model are unchanged, and ``chip.stream`` matches the programmed
dense oracle exactly instead of re-quantizing on different tile
boundaries.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.obs.core import current as _obs_current, span as _obs_span

from repro.core import routing as routing_lib
from repro.core.crossbar_layer import (CrossbarParams, DigitalParams,
                                       MLPSpec, ProgrammedMLP,
                                       digital_apply, program_layer,
                                       program_mlp, stream_precision)
from repro.core.device import DEFAULT_DEVICE, DeviceModel
from repro.core.mapping import (Mapping, Net, map_networks)
from repro.core.neural_core import CoreGeometry
from repro.core.systems import normalize_system, system_mode
from repro.core import quantization as q

# full compile passes (map → route → program) this process has run.
# ``repro.deploy``'s live-reprogram contract is "swap one tenant's
# weights with NO recompile of the fabric"; this counter is how that
# claim is *asserted* rather than assumed (selftest + tier-1).
_COMPILE_COUNT = 0


def compile_count() -> int:
    """Monotone count of :func:`compile_chip` passes in this process."""
    return _COMPILE_COUNT


def _static():
    return dataclasses.field(metadata=dict(static=True))


# --------------------------------------------------------------------- #
# the streamable execution plan (a jit-able pytree)
# --------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamLayer:
    """One network layer of the mapped dataflow.

    ``tiles`` is the programmed chip state (CrossbarParams tile grid or
    DigitalParams SRAM image). ``combine`` holds one programmed
    all-ones weight vector per Fig. 11 combiner level — the combining
    neurons are *real programmed neurons* (encoded through the same
    differential-pair + fold pipeline as any weight), not a free
    einsum reduction. ``levels`` gives each combine level's static
    (groups, fan_in) shape; empty when the layer fits the core rows.

    ``drift`` (crossbar layers under a drifting NoiseModel only) holds
    the per-cell conductance relaxation rates; streaming applies
    ``exp(-drift · age)`` to the tile grid, where ``age`` counts items
    streamed since the last programming event. None everywhere else —
    the plan (and its jit trace) is then exactly the ideal one.
    """
    tiles: Any                     # CrossbarParams | DigitalParams
    combine: Tuple[jax.Array, ...]           # (fan_in,) f32 per level
    bias: jax.Array                          # (d_out,) f32
    activation: str = _static()
    levels: Tuple[Tuple[int, int], ...] = _static()
    drift: Optional[jax.Array] = None        # per-cell rates | None


def _combiner_levels(n_chunks: int, geom: CoreGeometry,
                     device: DeviceModel) -> Tuple[Tuple[jax.Array, ...],
                                                   Tuple[Tuple[int, int],
                                                         ...]]:
    """Fig. 11 combiner tree for ``n_chunks`` sub-neuron partials.

    Mirrors ``split_network``'s recursion: while the partial count
    exceeds the core rows, an intermediate sub-neuron level sums
    balanced groups; the final level is the combining neuron proper.
    Every level's all-ones weight column is programmed through
    ``program_layer`` so the combine path evaluates *programmed*
    conductance state, exactly like any other neuron.
    """
    vecs: List[jax.Array] = []
    levels: List[Tuple[int, int]] = []
    k = n_chunks
    while k > 1:
        if k > geom.rows:
            groups = math.ceil(k / geom.rows)
            fan_in = math.ceil(k / groups)
        else:
            groups, fan_in = 1, k
        ones = program_layer(jnp.ones((fan_in, 1), jnp.float32),
                             geom=geom, device=device)
        w = ((ones.gp - ones.gn) *
             ones.scale[:, :, None, :])[0, 0, :fan_in, 0]
        vecs.append(w.astype(jnp.float32))
        levels.append((groups, fan_in))
        k = groups
    return tuple(vecs), tuple(levels)


def _layer_plan(lp, bias: jax.Array, activation: str,
                device: DeviceModel, *, noise=None,
                layer: int = 0) -> StreamLayer:
    if isinstance(lp, CrossbarParams):
        R = lp.gp.shape[0]
        geom = CoreGeometry(lp.geom_rows, lp.geom_cols)
        combine, levels = _combiner_levels(R, geom, device) if R > 1 \
            else ((), ())
        drift = None
        if noise is not None and noise.has_drift:
            # per-cell relaxation rates (epoch-independent: retention
            # is a device property). Combiner neurons are left ideal —
            # their all-ones encodings drift uniformly, which folds
            # into a common positive factor the activations ignore.
            drift = noise.drift_field(lp.gp.shape, layer=layer)
        return StreamLayer(lp, combine, bias.astype(jnp.float32),
                           activation, levels, drift)
    return StreamLayer(lp, (), bias.astype(jnp.float32), activation, ())


def _crossbar_partials(p: CrossbarParams, x: jax.Array,
                       use_kernel: bool,
                       decay: Optional[jax.Array] = None) -> jax.Array:
    """Sub-neuron stage: per-row-chunk partial dot products.

    x (B, d_in) → (B, R, d_out). Identical tile arithmetic to
    ``crossbar_apply`` but the Fig. 11 reduction over row chunks is NOT
    folded into the contraction — the partials feed the programmed
    combiner stage, which is the mapped dataflow.
    """
    R, C = p.gp.shape[0], p.gp.shape[1]
    rows, cols = p.geom_rows, p.geom_cols
    cdtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    xf = x.astype(cdtype)
    xp = jnp.pad(xf, ((0, 0), (0, R * rows - p.d_in)))
    xt = xp.reshape(-1, R, rows)
    gp, gn = p.gp, p.gn
    if decay is not None:
        # temporal drift: both pair devices relax toward G_OFF with
        # the cell's own rate; the program-time fold `scale` is frozen
        # physical state, so the decay is an uncorrected error — the
        # accuracy loss closed-loop recalibration exists to repair
        gp = gp * decay
        gn = gn * decay
    if use_kernel:
        # the fused kernel computes one row-chunk's (B, C·cols) slab;
        # vmap over the chunk axis keeps the partials separate for the
        # combiner stage while still running the Pallas hot path
        from repro.kernels import ops as kops
        parts = jax.vmap(
            lambda xr, gpr, gnr, sc: kops.crossbar_mvm(
                xr[:, None, :], gpr[None], gnr[None], sc[None]),
            in_axes=(1, 0, 0, 0), out_axes=1)(
                xt, gp, gn, p.scale)
    else:
        w_eff = ((gp - gn) * p.scale[:, :, None, :]).astype(cdtype)
        parts = jnp.einsum("brk,rckn->brcn", xt, w_eff,
                           preferred_element_type=jnp.float32,
                           precision=stream_precision(cdtype))
        parts = parts.reshape(xt.shape[0], R, C * cols)
    return parts[:, :, :p.d_out]


def _apply_stream_layer(layer: StreamLayer, x: jax.Array,
                        use_kernel: bool,
                        age: Optional[jax.Array] = None) -> jax.Array:
    if isinstance(layer.tiles, DigitalParams):
        return digital_apply(layer.tiles, x, bias=layer.bias,
                             activation=layer.activation,
                             use_kernel=use_kernel)
    decay = None
    if layer.drift is not None and age is not None:
        decay = jnp.exp(-layer.drift * age)
    parts = _crossbar_partials(layer.tiles, x, use_kernel,
                               decay)            # (B, R, d)
    for w, (groups, fan_in) in zip(layer.combine, layer.levels):
        B, K, d = parts.shape
        pad = groups * fan_in - K
        if pad:
            parts = jnp.pad(parts, ((0, 0), (0, pad), (0, 0)))
        parts = jnp.einsum("bgkd,k->bgd",
                           parts.reshape(B, groups, fan_in, d),
                           w.astype(parts.dtype),
                           preferred_element_type=jnp.float32,
                           precision=stream_precision(parts.dtype))
    out = parts[:, 0, :] if parts.ndim == 3 else parts
    out = out + layer.bias[None, :]
    return q.make_activation(layer.activation)(out)


def stream_pipeline(plan: Tuple[StreamLayer, ...], x: jax.Array,
                    use_kernel: bool = False,
                    replication: int = 1,
                    age: Optional[jax.Array] = None) -> jax.Array:
    """Stage-ordered evaluation of the whole mapped pipeline, with
    replica fan-out: the batch is dealt across the ``replication``
    identical pipeline copies (§V.C), each streaming its shard through
    the same programmed image.

    ``age`` (a traced f32 scalar: items streamed since programming)
    activates the per-cell drift decay on layers that carry a
    ``drift`` field; it is a traced value, so a drifting chip keeps
    ONE jit trace while aging. Aging is batch-granular — every item in
    a call sees the batch's entry age (the within-batch spread is
    ≤ batch/rate seconds of drift, negligible at the paper's rates).

    Un-jitted on purpose: :meth:`CompiledChip.stream` wraps it in the
    module-level jit below, and ``repro.fleet.shard`` calls it inside a
    ``shard_map`` body (one chip replica per mesh device), where the
    outer jit already owns the trace."""
    def replica(xb):
        h = xb
        for layer in plan:
            h = _apply_stream_layer(layer, h, use_kernel, age)
        return h

    B = x.shape[0]
    if replication <= 1 or B < replication:
        return replica(x)
    per = math.ceil(B / replication)
    pad = replication * per - B
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    out = jax.vmap(replica)(xp.reshape(replication, per, -1))
    return out.reshape(replication * per, -1)[:B]


_stream = functools.partial(jax.jit,
                            static_argnames=("use_kernel",
                                             "replication"))(stream_pipeline)


# --------------------------------------------------------------------- #
# the compiled chip object
# --------------------------------------------------------------------- #
class _ChipStatic:
    """Identity-hashed wrapper so rich compile metadata (Mapping,
    RouteReport — mutable report dataclasses) can ride through jit as
    static aux data: two chips are the same trace key iff they are the
    same compile."""
    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def __hash__(self):
        return id(self.value)

    def __eq__(self, other):
        return isinstance(other, _ChipStatic) and other.value is self.value


@dataclasses.dataclass
class CompiledChip:
    """A fully compiled + programmed chip (see module docstring).

    Registered as a pytree: ``plan`` (conductance tiles, fold scales,
    biases) are the array leaves; everything else — geometry,
    placement, the TDM schedule, the mapping — is static. jit-ing a
    function over a chip re-traces per compile, never per call.
    """
    system: str                         # memristor | digital
    geom: CoreGeometry
    mapping: Mapping
    route: routing_lib.RouteReport
    items_per_second: float             # target rate (0 → best effort)
    tsv_bits_per_item: Optional[float]
    plan: Optional[Tuple[StreamLayer, ...]]   # None → analytic-only
    dims: Optional[Tuple[int, ...]] = None
    # how the plan was encoded (weight_bits/device/r_seg) — what
    # reprogram_chip must reuse for a weights-ONLY swap to hold
    program_kw: Optional[dict] = None
    # the variability model the chip was compiled under (None = ideal
    # devices). Static compile metadata like program_kw; the mutable
    # drift state (items streamed since programming) lives in
    # __dict__ host-side, NOT in the pytree.
    noise: Optional[Any] = None
    # did THIS compile validate items_per_second against the routed TDM
    # schedule? ``repro.fleet.shard`` uses it to dedupe the fleet-level
    # re-validation: a chip-feasible rate times a fleet is vacuously
    # feasible, so re-checking the SAME rate would only duplicate the
    # warning the compile already issued.
    rate_validated: bool = False

    # ------------------------------------------------------------ #
    @property
    def replication(self) -> int:
        return self.mapping.replication

    @property
    def total_cores(self) -> int:
        return self.mapping.total_cores

    # -------- drift age (host-side mutable state) ---------------- #
    @property
    def items_streamed(self) -> int:
        """Items streamed since the last programming event — the
        drift clock. Always 0 for chips without a drifting noise
        model (the counter is only advanced when drift is active)."""
        return self.__dict__.get("_items_streamed", 0)

    @property
    def has_drift(self) -> bool:
        return self.noise is not None and self.noise.has_drift

    def reset_age(self) -> None:
        """Reset the drift clock, as a (re)programming event does."""
        self.__dict__["_items_streamed"] = 0

    def advance_age(self, items: int) -> None:
        """Advance the drift clock by ``items`` streamed elsewhere
        (``repro.fleet.shard`` streams the replicated plan itself and
        accounts the aging back onto the source chip)."""
        if self.has_drift:
            self.__dict__["_items_streamed"] = \
                self.items_streamed + int(items)

    def stream(self, x: jax.Array, *, use_kernel: bool = False,
               fan_out: bool = True,
               advance_age: bool = True) -> jax.Array:
        """Stream a batch through the mapped, programmed pipeline.

        x: (..., d_in) → (..., d_out). ``fan_out=False`` pins the whole
        batch onto one replica (the other replicas idle), e.g. to
        measure single-replica latency. Under a drifting noise model
        the call evaluates at the chip's current age and then advances
        the drift clock by the batch size; ``advance_age=False`` makes
        it a pure probe (canary scoring must not itself age the chip).
        """
        if self.plan is None:
            raise ValueError(
                "this chip was compiled from bare network shapes "
                "(no weights), so it is analytic-only: report() works, "
                "but stream() needs programmed state. "
                "Re-compile with compile_chip(spec, params=...) or "
                "from a ProgrammedMLP.")
        lead = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1])
        rep = self.mapping.replication if fan_out else 1
        age = None
        if self.has_drift:
            age = jnp.asarray(float(self.items_streamed), jnp.float32)
        # program-vs-stream economics, measured: the traced stream
        # span carries the chip's compile_count delta (pinned 0 — a
        # stream must never re-run the program pass) next to the
        # per-batch wall time the compile span prices against
        tel = _obs_current()
        c0 = _COMPILE_COUNT
        with _obs_span("chip.stream", cat="chip", rows=int(xf.shape[0]),
                       system=self.system) as sp:
            out = _stream(self.plan, xf, use_kernel=use_kernel,
                          replication=rep, age=age)
            if tel.active:
                jax.block_until_ready(out)
                sp.args["compile_delta"] = _COMPILE_COUNT - c0
        if age is not None and advance_age:
            self.advance_age(xf.shape[0])
        return out.reshape(*lead, out.shape[-1]).astype(x.dtype)

    def __call__(self, x: jax.Array, **kw) -> jax.Array:
        return self.stream(x, **kw)

    def report(self):
        """Unified area/power/throughput accounting (Tables II–VI)."""
        from repro.chip.report import chip_report
        return chip_report(self)


def _chip_flatten(chip: CompiledChip):
    # cache the wrapper on the instance: _ChipStatic compares by
    # identity, so a fresh wrapper per flatten would make every jit
    # call over the chip a new trace key (retrace per call, not per
    # compile). One wrapper per chip keeps the trace cache warm.
    static = chip.__dict__.get("_static")
    if static is None:
        static = _ChipStatic((chip.system, chip.geom, chip.mapping,
                              chip.route, chip.items_per_second,
                              chip.tsv_bits_per_item, chip.dims,
                              chip.program_kw, chip.noise,
                              chip.rate_validated))
        chip.__dict__["_static"] = static
    return (chip.plan,), static


def _chip_unflatten(static: _ChipStatic, children) -> CompiledChip:
    (system, geom, mapping, route, rate, tsv, dims, pkw,
     noise, rate_validated) = static.value
    chip = CompiledChip(system, geom, mapping, route, rate, tsv,
                        children[0], dims, pkw, noise, rate_validated)
    chip.__dict__["_static"] = static
    return chip


jax.tree_util.register_pytree_node(CompiledChip, _chip_flatten,
                                   _chip_unflatten)


# --------------------------------------------------------------------- #
# compile_chip
# --------------------------------------------------------------------- #
NetworksLike = Union[MLPSpec, ProgrammedMLP, Net, Sequence[Net]]


class ChipRateWarning(UserWarning):
    """The requested items_per_second exceeds what the routed fabric's
    TDM link schedule can sustain."""


def validate_stream_rate(items_per_second: float, replicas: int,
                         route: routing_lib.RouteReport,
                         strict: bool, *,
                         context: str = "compile_chip",
                         fabric: str = "replica(s)",
                         remedy: str = ("Use a larger core geometry "
                                        "(fewer row chunks -> less mesh "
                                        "traffic), lower the target "
                                        "rate, or split the load across "
                                        "chips (repro.fleet)."),
                         stacklevel: int = 3,
                         chip_replicas: Optional[int] = None) -> None:
    """items_per_second sizes the replica fan-out against COMPUTE
    capacity (§V.C), but each replica's mesh is also a static TDM
    network whose busiest link forwards LINK_BITS per cycle — a rate a
    replica's cores could hit may still be un-routable. Validate the
    per-replica rate against the routed schedule.

    ``replicas`` is however many identical copies of the routed fabric
    share the load: ``mapping.replication`` at compile time, and
    ``replication × n_chips`` when ``repro.fleet.shard_chip`` fans the
    same compiled plan across a device mesh (the fleet-level
    re-validation — a chip-feasible rate times a fleet does not need
    checking, but a fleet-level target divided across the chips does).

    ``chip_replicas`` (the per-chip replication, when ``replicas`` is
    already the fleet total) folds BOTH capacity levels into the one
    diagnostic, so a deployment that validates once — at the fleet
    level — still tells the user what a single chip could have carried.
    """
    if not items_per_second:
        return
    per_replica = items_per_second / replicas
    limit = route.max_items_per_second
    if per_replica <= limit * (1.0 + 1e-9):
        return
    capacities = ""
    if chip_replicas is not None:
        capacities = (f" Capacity: {chip_replicas * limit:g} items/s "
                      f"per chip, {replicas * limit:g} items/s "
                      f"fleet-wide.")
    msg = (f"{context}: items_per_second={items_per_second:g} is "
           f"infeasible on the routed fabric: each of the "
           f"{replicas} {fabric} must stream "
           f"{per_replica:g} items/s, but the busiest mesh link's TDM "
           f"frame is {route.schedule_cycles} cycles/item, capping a "
           f"replica at {limit:g} items/s.{capacities} {remedy}")
    if strict:
        raise ValueError(msg)
    warnings.warn(msg, ChipRateWarning, stacklevel=stacklevel)


def _validate_rate(items_per_second: float, mapping,
                   route: routing_lib.RouteReport,
                   strict: bool) -> None:
    # point the warning at compile_chip's caller: stacklevel counts
    # validate_stream_rate(1) → here(2) → compile_chip(3) → user(4)
    validate_stream_rate(items_per_second, mapping.replication, route,
                         strict, stacklevel=4)


def _spec_dims(prog: ProgrammedMLP) -> Tuple[int, ...]:
    dims = [prog.layers[0].d_in]
    for lp in prog.layers:
        dims.append(lp.d_out)
    return tuple(dims)


def compile_chip(networks: NetworksLike, *,
                 params=None,
                 system: str = "memristor",
                 geom: Optional[CoreGeometry] = None,
                 items_per_second: float = 0.0,
                 weight_bits: int = 8,
                 device: DeviceModel = DEFAULT_DEVICE,
                 noise_key: Optional[jax.Array] = None,
                 r_seg: float = 0.0,
                 noise=None,
                 sensor_flags: Optional[Sequence[bool]] = None,
                 deps: Optional[Sequence[Sequence[int]]] = None,
                 tsv_bits_per_item: Optional[float] = None,
                 strict_rate: bool = False,
                 validate_rate: bool = True
                 ) -> CompiledChip:
    """Compile networks onto a chip: split → pack → place → route, then
    program every mapped group's tile state.

    ``networks`` is one of
      * an :class:`MLPSpec` — pass ``params`` (from ``mlp_init`` or the
        QAT trainer) to get a streamable chip, omit it for an
        analytic-only compile;
      * a :class:`ProgrammedMLP` — re-uses its already-programmed tile
        state (no re-encoding), geometry/system inferred;
      * a ``(instances, dims)`` net tuple or a sequence of them — the
        paper's app notation; analytic-only (report/serve sizing, no
        functional stream).

    ``system`` is ``"memristor"`` (1T1M crossbar cores) or
    ``"digital"`` (SRAM cores); ``items_per_second`` sizes the replica
    fan-out to the application's real-time rate (§V.C) and is validated
    against the routed TDM link capacity: an un-routable rate warns
    (:class:`ChipRateWarning`) or, with ``strict_rate=True``, raises.
    ``validate_rate=False`` defers that check to a caller that will
    validate the SAME rate at a wider scope (``repro.deploy`` validates
    once at the fleet level, with both capacity numbers in the one
    diagnostic) — the chip records whether it was validated
    (``rate_validated``) so downstream re-checks can dedupe.

    ``noise`` (a ``repro.variability.NoiseModel``) compiles the chip
    onto NON-ideal devices: programming-time effects perturb the
    encoding when this compile runs the encoder (MLPSpec + params),
    and temporal drift attaches per-cell relaxation rates the stream
    path evaluates against the chip's age. An ideal model (all
    effects zero) is structurally skipped — bit-identical to
    ``noise=None``. Digital (SRAM) systems ignore the model.
    """
    system = normalize_system(system, context="compile_chip")
    mode = system_mode(system)
    global _COMPILE_COUNT
    _COMPILE_COUNT += 1
    _t_compile0 = time.perf_counter()

    prog: Optional[ProgrammedMLP] = None
    dims: Optional[Tuple[int, ...]] = None
    encoded_here = False                # did THIS compile run the encoder?
    if isinstance(networks, ProgrammedMLP):
        prog = networks
        if (prog.mode == "crossbar") != (system == "memristor"):
            raise ValueError(
                f"compile_chip: ProgrammedMLP mode {prog.mode!r} does "
                f"not match system {system!r}")
        dims = _spec_dims(prog)
        if geom is None and prog.mode == "crossbar":
            lp0 = prog.layers[0]
            geom = CoreGeometry(lp0.geom_rows, lp0.geom_cols)
        nets: Tuple[Net, ...] = ((1, dims),)
    elif isinstance(networks, MLPSpec):
        dims = tuple(networks.dims)
        nets = ((1, dims),)
        if params is not None:
            prog = program_mlp(params, networks, mode=mode,
                               geom=geom or _default_geom(system),
                               device=device, weight_bits=weight_bits,
                               noise_key=noise_key, r_seg=r_seg,
                               noise=noise, noise_epoch=0)
            encoded_here = True
    else:
        if params is not None:
            raise ValueError(
                "compile_chip: params are only meaningful with an "
                "MLPSpec (one weighted network); bare net tuples "
                "compile analytic-only chips")
        if hasattr(networks, "family") and hasattr(networks, "num_layers"):
            raise NotImplementedError(
                f"compile_chip maps MLPs only; "
                f"{getattr(networks, 'family', '?')!r} model configs "
                f"(transformer blocks, KV caches) are compiled by "
                f"repro.lm.compile_lm")
        seq = list(networks)
        if seq and isinstance(seq[0], int):       # a single bare Net
            seq = [tuple(networks)]
        nets = tuple((int(i), tuple(d)) for i, d in seq)

    mapping = map_networks(nets, system=system, geom=geom,
                           items_per_second=items_per_second,
                           sensor_flags=sensor_flags, deps=deps)
    route = routing_lib.route(mapping)
    if validate_rate:
        _validate_rate(items_per_second, mapping, route, strict_rate)

    plan: Optional[Tuple[StreamLayer, ...]] = None
    if prog is not None:
        plan = program_plan(prog, device=device, noise=noise)
    # encoding knobs recorded only when this compile ran the encoder —
    # for a caller-programmed MLP they describe nothing (reprogram_chip
    # then demands them explicitly instead of guessing)
    chip = CompiledChip(system, mapping.geom, mapping, route,
                        items_per_second, tsv_bits_per_item, plan, dims,
                        dict(weight_bits=weight_bits, device=device,
                             r_seg=r_seg) if encoded_here else None,
                        noise, rate_validated=bool(validate_rate))
    tel = _obs_current()
    if tel.active:
        dur = time.perf_counter() - _t_compile0
        tel.tracer.complete("chip.compile", _t_compile0, dur, tid=0,
                            cat="chip",
                            args={"system": system,
                                  "dims": list(dims) if dims else None,
                                  "streamable": plan is not None})
        tel.metrics.counter("chip.compiles").inc()
        tel.metrics.histogram("chip.compile_s").record(dur)
    return chip


def program_plan(prog: ProgrammedMLP, *,
                 device: DeviceModel = DEFAULT_DEVICE,
                 noise=None) -> Tuple[StreamLayer, ...]:
    """The programming half of a compile, alone: turn an already
    programmed MLP into the streamable per-layer plan (tiles +
    Fig. 11 combiner neurons). ``compile_chip`` calls this after
    map+route; :func:`reprogram_chip` calls it INSTEAD of them.
    ``noise`` attaches per-cell drift rates to crossbar layers when
    the model drifts (programming-time effects belong to
    ``program_mlp``, which already ran)."""
    return tuple(_layer_plan(lp, b, act, device, noise=noise, layer=i)
                 for i, (lp, b, act) in
                 enumerate(zip(prog.layers, prog.biases,
                               prog.activations)))


_KEEP_NOISE = object()     # sentinel: "reuse the chip's own model"


def reprogram_chip(chip: CompiledChip, params, *,
                   spec: Optional[MLPSpec] = None,
                   weight_bits: Optional[int] = None,
                   device: Optional[DeviceModel] = None,
                   noise_key: Optional[jax.Array] = None,
                   r_seg: Optional[float] = None,
                   noise=_KEEP_NOISE) -> CompiledChip:
    """Swap a compiled chip's weights WITHOUT recompiling the fabric.

    The paper's §III.D economics split a chip's life into program-once
    and stream-many; this is the third verb that story implies: the
    mapping, placement and routed TDM schedule are functions of the
    network *shape* only, so new weights for the same topology need
    only re-encoding into tile state (``program_mlp`` +
    :func:`program_plan`) — map_networks/route never run, which is what
    keeps a live tenant-weight swap (``repro.deploy``'s ``reprogram``)
    milliseconds instead of a full compile, and is asserted by
    :func:`compile_count` staying put.

    The returned chip shares the original's mapping/route objects;
    only ``plan`` is new. ``spec`` defaults to the chip's own dims and
    per-layer activations, and ``weight_bits``/``device``/``r_seg``
    default to the values the chip was COMPILED with — a bare
    reprogram re-encodes exactly the way the original programming did
    (``noise_key`` is per-programming-event, so it never defaults to
    the old one).

    The chip's variability model carries over by default (pass
    ``noise=`` to change it, including ``None`` to go ideal). A
    reprogram is a new programming *epoch*: write noise re-rolls,
    stuck cells persist, and the drift clock resets to age 0 — the
    physics that makes closed-loop recalibration work.
    """
    if chip.plan is None:
        raise ValueError(
            "reprogram_chip: this chip is analytic-only (compiled "
            "without weights) — there is no programmed state to swap; "
            "compile_chip(spec, params=...) first")
    if chip.program_kw is None and \
            (weight_bits is None or device is None or r_seg is None):
        # the chip was compiled from an externally-programmed MLP, so
        # how its tiles were encoded is unknown — guessing defaults
        # would silently change the tenant's quantization
        raise ValueError(
            "reprogram_chip: this chip was compiled from a "
            "pre-programmed MLP, so its original encoding parameters "
            "are not recorded — pass weight_bits, device and r_seg "
            "explicitly to guarantee the swap re-encodes the same way")
    compiled_kw = chip.program_kw or {}
    if weight_bits is None:
        weight_bits = compiled_kw["weight_bits"]
    if device is None:
        device = compiled_kw["device"]
    if r_seg is None:
        r_seg = compiled_kw["r_seg"]
    explicit_spec = spec
    if spec is None:
        spec = MLPSpec(chip.dims,
                       activation=chip.plan[0].activation,
                       out_activation=chip.plan[-1].activation)
    if tuple(spec.dims) != tuple(chip.dims):
        raise ValueError(
            f"reprogram_chip: new network dims {tuple(spec.dims)} do "
            f"not match the compiled fabric {tuple(chip.dims)} — a "
            f"different topology re-maps and re-routes; use "
            f"compile_chip")
    if len(params) != len(chip.dims) - 1:
        raise ValueError(
            f"reprogram_chip: {len(params)} weight layer(s) do not "
            f"match the compiled fabric's {len(chip.dims) - 1}")
    for i, p in enumerate(params):
        want = (chip.dims[i], chip.dims[i + 1])
        if tuple(p["w"].shape) != want:
            raise ValueError(
                f"reprogram_chip: layer {i} weights {tuple(p['w'].shape)}"
                f" do not match the compiled fabric {want}")
    if noise is _KEEP_NOISE:
        noise = chip.noise
    epoch = chip.__dict__.get("_noise_epoch", 0) + 1
    prog = program_mlp(params, spec, mode=system_mode(chip.system),
                       geom=chip.geom, device=device,
                       weight_bits=weight_bits, noise_key=noise_key,
                       r_seg=r_seg, noise=noise, noise_epoch=epoch)
    if explicit_spec is None:
        # tile programming is activation-independent, but the plan
        # records one activation PER layer — preserve the compiled
        # chip's own schedule rather than the MLPSpec reconstruction,
        # which can only express hidden/out (a hand-built
        # heterogeneous ProgrammedMLP would be silently re-activated)
        prog = dataclasses.replace(
            prog, activations=tuple(l.activation for l in chip.plan))
    new = dataclasses.replace(chip,
                              plan=program_plan(prog, device=device,
                                                noise=noise),
                              noise=noise)
    # fresh object → fresh __dict__: the drift clock starts at age 0;
    # remember the epoch so the NEXT reprogram re-rolls write noise
    new.__dict__["_noise_epoch"] = epoch
    tel = _obs_current()
    if tel.active:
        tel.tracer.instant(
            "chip.reprogram", cat="chip",
            args={"system": chip.system, "epoch": epoch,
                  "compile_count": _COMPILE_COUNT})
        tel.metrics.counter("chip.reprograms").inc()
    return new


def _default_geom(system: str) -> CoreGeometry:
    from repro.core.neural_core import DIGITAL_GEOM, MEMRISTOR_GEOM
    return MEMRISTOR_GEOM if system == "memristor" else DIGITAL_GEOM


def compile_app(app, system: str, *,
                geom: Optional[CoreGeometry] = None) -> CompiledChip:
    """Compile one of the paper's applications (an
    ``repro.configs.paper_apps.AppConfig``, duck-typed) at its real-time
    load: the analytic chip whose ``report()`` is the app's Tables
    II–VI row for ``system``."""
    system = normalize_system(system, context="compile_app")
    nets = app.memristor_nets if system == "memristor" else app.sram_nets
    return compile_chip(nets, system=system, geom=geom,
                        items_per_second=app.items_per_second,
                        sensor_flags=app.sensor_flags(system),
                        deps=app.net_deps(system),
                        tsv_bits_per_item=app.tsv_bits_per_item)
