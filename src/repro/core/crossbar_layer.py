"""Program-once / stream-many execution of arbitrary linear layers.

Bridges the paper's fixed-geometry cores and real model layers, with
the paper's central split made structural (§III.D: train off-chip →
program once → stream inference):

  PROGRAM (slow, once per deployment)
    program_layer     — tile a float (d_in × d_out) weight matrix into
                        crossbar-geometry tiles, differential-encode
                        each tile as (σ⁺, σ⁻) conductances (with
                        optional quantization, programming noise and
                        wire resistance), and fold *every*
                        input-independent factor — Eq. 3's divider
                        Σ(σ⁺+σ⁻), the per-tile weight descale and the
                        wire-attenuation correction — into ONE
                        per-tile-column `scale`.
    program_digital   — the SRAM-core counterpart: int8 synapses plus
                        precomputed per-neuron requantize (scale,
                        offset) constants.
    program_mlp       — program every layer of an MLP once, returning
                        a ProgrammedMLP that is reused across calls.

  EVALUATE (fast, the streaming hot path)
    crossbar_apply    — x (..., d_in) → (..., d_out): a single batched
                        einsum over the whole (R, C) tile grid (or the
                        fused Pallas kernel via use_kernel=True); the
                        per-tile evaluation is
                          Σ over row-chunks r of (x_r @ (σ⁺−σ⁻)) · scale
                        — the float-domain equivalent of Fig. 11's
                        combining neurons — followed by a fused
                        bias + activation epilogue.
    digital_apply     — int8 MAC + fused requantize/bias/activation.

Because the divider and descale are folded at program time, evaluation
never recomputes input-independent arithmetic — exactly the property
that lets the paper's analog crossbar stream one inference per cycle.

`crossbar_linear` / `digital_linear` remain as one-shot
program-and-apply conveniences for tests and tiny scripts ONLY: they
re-program the chip on every call, which is the anti-pattern this
module exists to avoid. Anything called repeatedly must hold a
CrossbarParams / DigitalParams / ProgrammedMLP.

`kernels/crossbar_mvm` implements the same tile evaluation as a fused
Pallas kernel; `ops.use_kernel()` routes through it. This module is the
pure-jnp oracle and the API the examples and the QAT trainer use.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.core.crossbar import (column_gain, pairs_from_weights,
                                 wire_attenuation)
from repro.core.device import DeviceModel, DEFAULT_DEVICE
from repro.core.neural_core import CoreGeometry, MEMRISTOR_GEOM


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _deprecated(name: str, instead: str) -> None:
    warnings.warn(
        f"{name} is deprecated; use {instead} (the unified chip API: "
        "compile once, stream many)", DeprecationWarning, stacklevel=3)


def _static():
    return dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CrossbarParams:
    """Programmed chip state for one linear layer.

    `scale` is the program-time fold of everything Eq. 3 needs beyond
    the raw MXU dot product: per tile-column j,

        scale = amax · Σ(σ⁺+σ⁻)_intended / (g_range · Σ(σ⁺+σ⁻)_actual)

    where "intended" is the pre-noise encoding (the chip's downstream
    scales are fixed at program time) and "actual" is the physically
    programmed column loading (incl. noise and wire attenuation) that
    the divider actually sees. Evaluation is then just
    Σ_r (x_r @ (σ⁺−σ⁻)) · scale.

    Registered as a pytree with static geometry so programmed state
    flows straight through jax.jit — the streaming evaluate compiles
    to one fused XLA computation per layer stack.
    """
    gp: jax.Array       # (R, C, rows, cols) conductance tiles
    gn: jax.Array
    scale: jax.Array    # (R, C, cols) — folded divider + descale
    d_in: int = _static()
    d_out: int = _static()
    geom_rows: int = _static()
    geom_cols: int = _static()


def program_layer(w: jax.Array, *, geom: CoreGeometry = MEMRISTOR_GEOM,
                  device: DeviceModel = DEFAULT_DEVICE,
                  quantize: bool = True,
                  noise_key: Optional[jax.Array] = None,
                  noise_tol: float = 1.0 / 256.0,
                  r_seg: float = 0.0,
                  noise=None, noise_layer: int = 0,
                  noise_epoch: int = 0) -> CrossbarParams:
    """Tile + differential-encode + (optionally) perturb like the
    feedback-write residual, then fold all input-independent scales.
    w: (d_in, d_out) float. Wire resistance (r_seg > 0) is a
    program-time transform of the conductances, so it is folded here —
    evaluation always computes the ideal datapath.

    ``noise`` (a ``repro.variability.NoiseModel``, duck-typed so core
    never imports upward) applies the structured non-idealities:
    lognormal write error (re-rolled per ``noise_epoch``, i.e. per
    programming event), persistent stuck cells, and IR-drop
    attenuation. An ideal model is skipped entirely — bit-identical
    to ``noise=None``. Temporal drift is NOT applied here; it is a
    stream-time effect handled by ``repro.chip.stream_pipeline``."""
    d_in, d_out = w.shape
    R = math.ceil(d_in / geom.rows)
    C = math.ceil(d_out / geom.cols)
    wp = _pad_to(w, R * geom.rows, C * geom.cols)
    tiles = wp.reshape(R, geom.rows, C, geom.cols).transpose(0, 2, 1, 3)

    def enc(tile):
        gp, gn, amax = pairs_from_weights(tile, device, quantize)
        # descale from the *intended* state: the chip's downstream
        # scales are fixed at program time (the noise residual is the
        # accuracy cost the paper's tolerance bound accepts)
        descale = amax * column_gain(gp, gn) / device.g_range
        return gp, gn, descale

    gp, gn, descale = jax.vmap(jax.vmap(enc))(tiles)
    if noise_key is not None:
        from repro.core.programming import ProgrammingConfig, \
            programming_noise
        cfg = ProgrammingConfig(tol_frac=noise_tol, device=device)
        kp, kn = jax.random.split(noise_key)
        gp = device.clip(gp + programming_noise(kp, gp.shape, cfg))
        gn = device.clip(gn + programming_noise(kn, gn.shape, cfg))
    if noise is not None and not noise.is_ideal:
        gp, gn = noise.perturb(gp, gn, device, layer=noise_layer,
                               epoch=noise_epoch)
        if noise.ir_drop_r_seg:
            att = wire_attenuation(geom.rows, geom.cols,
                                   float(device.g_on),
                                   noise.ir_drop_r_seg)
            gp = gp * att
            gn = gn * att
    if r_seg:
        att = wire_attenuation(geom.rows, geom.cols,
                               float(device.g_on), r_seg)
        gp = gp * att
        gn = gn * att
    # the divider the physical column actually realizes
    den_actual = jnp.sum(gp + gn, axis=2)               # (R, C, cols)
    scale = descale / den_actual
    return CrossbarParams(gp, gn, scale, d_in, d_out,
                          geom.rows, geom.cols)


def stream_precision(dtype) -> Optional[jax.lax.Precision]:
    """f32 stream contractions run at full precision: a TPU otherwise
    takes one bf16 pass, and threshold activations then flip on
    pre-activations near zero. bf16 streams keep the default pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def crossbar_apply(params: CrossbarParams, x: jax.Array, *,
                   bias: Optional[jax.Array] = None,
                   activation: str = "linear",
                   use_kernel: bool = False) -> jax.Array:
    """Streaming evaluate: x (..., d_in) → (..., d_out).

    Pure evaluate path — no re-tiling, no re-encoding, no divider
    arithmetic; bias and activation are fused into the epilogue."""
    R, C = params.gp.shape[0], params.gp.shape[1]
    rows, cols = params.geom_rows, params.geom_cols
    lead = x.shape[:-1]
    cdtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    xf = x.reshape(-1, x.shape[-1]).astype(cdtype)
    xp = jnp.pad(xf, ((0, 0), (0, R * rows - params.d_in)))
    xt = xp.reshape(-1, R, rows)

    if use_kernel:
        from repro.kernels import ops as kops
        bfull = None
        if bias is not None:
            bfull = jnp.pad(bias.astype(jnp.float32).reshape(-1),
                            (0, C * cols - params.d_out))
        out = kops.crossbar_mvm(xt, params.gp, params.gn, params.scale,
                                bfull, activation=activation)
        out = out[:, :params.d_out]
    else:
        # one batched contraction over the whole (R, C) tile grid: the
        # per-tile scale folds into the effective weights, and the sum
        # over row-chunks (Fig. 11 combining) is the einsum reduction.
        w_eff = ((params.gp - params.gn) *
                 params.scale[:, :, None, :]).astype(cdtype)
        out = jnp.einsum("brk,rckn->bcn", xt, w_eff,
                         preferred_element_type=jnp.float32,
                         precision=stream_precision(cdtype))
        out = out.reshape(xt.shape[0], C * cols)[:, :params.d_out]
        if bias is not None:
            out = out + bias.astype(jnp.float32)[None, :]
        out = q.make_activation(activation)(out)
    return out.reshape(*lead, params.d_out).astype(x.dtype)


def crossbar_linear(x: jax.Array, w: jax.Array, *,
                    geom: CoreGeometry = MEMRISTOR_GEOM,
                    device: DeviceModel = DEFAULT_DEVICE,
                    quantize: bool = True, r_seg: float = 0.0,
                    activation: str = "linear",
                    noise_key: Optional[jax.Array] = None,
                    use_kernel: bool = False) -> jax.Array:
    """DEPRECATED one-shot program + apply: re-programs the crossbars
    on every call, which silently throws away the paper's program-once
    economics. Hold a CrossbarParams from program_layer, or compile the
    whole network with repro.chip.compile_chip."""
    _deprecated("crossbar_linear",
                "program_layer(...) + crossbar_apply, or "
                "repro.chip.compile_chip(...).stream")
    params = program_layer(w, geom=geom, device=device, quantize=quantize,
                           noise_key=noise_key, r_seg=r_seg)
    return crossbar_apply(params, x, activation=activation,
                          use_kernel=use_kernel)


# --------------------------------------------------------------------- #
# the digital (SRAM) core counterpart
# --------------------------------------------------------------------- #
# input DAC range for the digital datapath (§II.A): analog voltages in
# [-1, 1] quantized to 2^bits codes.
_DIG_LO, _DIG_HI = -1.0, 1.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DigitalParams:
    """Programmed SRAM-core state: int8 synapses + the requantize
    constants fixed when the synapse memory is written.

    Evaluation is  act(acc · scale + offset [+ bias·…])  where
    acc = xq @ wq is the raw int32 MAC-array output; scale/offset fold
    the weight scale, the input step and the zero-point correction."""
    wq: jax.Array       # (d_in, d_out) int codes
    scale: jax.Array    # (d_out,) f32 — step · weight_scale
    offset: jax.Array   # (d_out,) f32 — lo · Σ_k wq · weight_scale
    step: float = _static()   # input quantization step
    bits: int = _static()
    d_in: int = _static()
    d_out: int = _static()


def program_digital(w: jax.Array, *, bits: int = 8) -> DigitalParams:
    """Quantize weights and precompute the per-neuron requantize
    epilogue constants (program-once for the SRAM core)."""
    d_in, d_out = w.shape
    wq, ws = q.quantize_weights(w, bits=bits, per_column=True)
    n = 2.0 ** bits - 1.0
    step = (_DIG_HI - _DIG_LO) / n
    ws = ws.reshape(-1).astype(jnp.float32)
    scale = step * ws
    offset = _DIG_LO * jnp.sum(wq, axis=0).astype(jnp.float32) * ws
    return DigitalParams(wq, scale, offset, step, bits, d_in, d_out)


def digital_apply(params: DigitalParams, x: jax.Array, *,
                  bias: Optional[jax.Array] = None,
                  activation: str = "linear",
                  use_kernel: bool = False) -> jax.Array:
    """Streaming evaluate on the digital core: quantize inputs, int
    MAC, fused requantize + bias + activation epilogue."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    n = 2.0 ** params.bits - 1.0
    xq = jnp.clip(jnp.round((xf - _DIG_LO) / params.step), 0, n)
    offset = params.offset
    if bias is not None:
        offset = offset + bias.astype(jnp.float32).reshape(-1)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.int8_matmul(xq.astype(jnp.uint8), params.wq,
                               params.scale, offset,
                               activation=activation)
    else:
        acc = xq.astype(jnp.int32) @ params.wq.astype(jnp.int32)
        out = acc.astype(jnp.float32) * params.scale[None, :] + \
            offset[None, :]
        out = q.make_activation(activation)(out)
    return out.reshape(*lead, params.d_out).astype(x.dtype)


def digital_linear(x: jax.Array, w: jax.Array, *, bits: int = 8,
                   activation: str = "linear",
                   use_kernel: bool = False) -> jax.Array:
    """DEPRECATED one-shot SRAM-core execution (§II.A datapath):
    re-quantizes the weights on every call. Hold a DigitalParams from
    program_digital, or compile with repro.chip.compile_chip."""
    _deprecated("digital_linear",
                "program_digital(...) + digital_apply, or "
                "repro.chip.compile_chip(..., system='digital').stream")
    params = program_digital(w, bits=bits)
    return digital_apply(params, x, activation=activation,
                         use_kernel=use_kernel)


# --------------------------------------------------------------------- #
# QAT-trained MLP in crossbar mode (the paper's app networks)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class MLPSpec:
    dims: Tuple[int, ...]
    activation: str = "threshold"    # hidden activation (memristor)
    out_activation: str = "linear"


def mlp_init(key: jax.Array, spec: MLPSpec):
    params = []
    for i in range(len(spec.dims) - 1):
        key, k = jax.random.split(key)
        fan = spec.dims[i]
        params.append({
            "w": jax.random.normal(k, (spec.dims[i], spec.dims[i + 1]),
                                   jnp.float32) / jnp.sqrt(fan),
            "b": jnp.zeros((spec.dims[i + 1],), jnp.float32),
        })
    return params


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ProgrammedMLP:
    """A fully programmed MLP: per-layer chip state + biases + the
    fused activation schedule. Build once with program_mlp, stream
    through programmed_mlp_apply — no per-call re-encoding."""
    layers: Tuple       # CrossbarParams | DigitalParams per layer
    biases: Tuple       # (d_out,) f32 per layer
    activations: Tuple[str, ...] = _static()  # fused act per layer
    mode: str = _static()                     # "crossbar" | "digital"


def program_mlp(params, spec: MLPSpec, *, mode: str = "crossbar",
                geom: CoreGeometry = MEMRISTOR_GEOM,
                device: DeviceModel = DEFAULT_DEVICE,
                weight_bits: int = 8,
                noise_key: Optional[jax.Array] = None,
                r_seg: float = 0.0,
                noise=None, noise_epoch: int = 0) -> ProgrammedMLP:
    """Program every layer of the MLP once (crossbar or SRAM mode).
    ``noise``/``noise_epoch`` thread the variability model into each
    crossbar layer's programming (digital mode ignores them: SRAM
    writes are noise-free in this model)."""
    if mode not in ("crossbar", "digital"):
        raise ValueError(f"program_mlp: unknown mode {mode!r}")
    n = len(params)
    layers, biases, acts = [], [], []
    for i, p in enumerate(params):
        if mode == "crossbar":
            key = None
            if noise_key is not None:
                noise_key, key = jax.random.split(noise_key)
            layers.append(program_layer(p["w"], geom=geom, device=device,
                                        noise_key=key, r_seg=r_seg,
                                        noise=noise, noise_layer=i,
                                        noise_epoch=noise_epoch))
        else:
            layers.append(program_digital(p["w"], bits=weight_bits))
        biases.append(p["b"].astype(jnp.float32))
        acts.append(spec.activation if i < n - 1 else spec.out_activation)
    return ProgrammedMLP(tuple(layers), tuple(biases), tuple(acts), mode)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _programmed_mlp_eval(prog: ProgrammedMLP, x: jax.Array,
                         use_kernel: bool = False) -> jax.Array:
    apply_fn = crossbar_apply if prog.mode == "crossbar" else digital_apply
    h = x
    for lp, b, act in zip(prog.layers, prog.biases, prog.activations):
        h = apply_fn(lp, h, bias=b, activation=act, use_kernel=use_kernel)
    return h


def programmed_mlp_apply(prog: ProgrammedMLP, x: jax.Array, *,
                         use_kernel: bool = False) -> jax.Array:
    """The streaming hot path: the whole layer stack compiles to one
    fused XLA computation over already-programmed state (the chip-state
    containers are pytrees with static geometry, so jit sees only
    array leaves and re-traces per shape, never per call)."""
    return _programmed_mlp_eval(prog, x, use_kernel=use_kernel)


# Small FIFO memo so mlp_apply(mode="crossbar"|"digital") programs each
# param set once even when the caller doesn't hold a ProgrammedMLP. The
# key is the *identity* of the weight arrays; entries keep strong refs
# to their anchors so a live key can never alias a recycled id().
_MLP_PROGRAM_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_MLP_PROGRAM_CACHE_MAX = 8


def clear_program_cache() -> None:
    """Drop all memoized ProgrammedMLPs (and the strong refs they hold
    to their source param arrays). Long-lived processes that cycle
    through many models should call this — or hold ProgrammedMLPs
    explicitly via program_mlp and skip the memo entirely."""
    _MLP_PROGRAM_CACHE.clear()


def _cached_program_mlp(params, spec: MLPSpec, mode: str,
                        weight_bits: int) -> ProgrammedMLP:
    anchors = tuple(p["w"] for p in params) + tuple(p["b"] for p in params)
    if any(isinstance(a, jax.core.Tracer) for a in anchors):
        # under jit/vmap/grad tracing: program inside the trace (pure,
        # correct) but never let tracer-built state into the memo —
        # it would leak tracers and evict live concrete entries.
        return program_mlp(params, spec, mode=mode,
                           weight_bits=weight_bits)
    key = (mode, weight_bits, spec, tuple(id(a) for a in anchors))
    hit = _MLP_PROGRAM_CACHE.get(key)
    if hit is not None and all(a is b for a, b in zip(hit[0], anchors)):
        _MLP_PROGRAM_CACHE.move_to_end(key)
        return hit[1]
    prog = program_mlp(params, spec, mode=mode, weight_bits=weight_bits)
    _MLP_PROGRAM_CACHE[key] = (anchors, prog)
    while len(_MLP_PROGRAM_CACHE) > _MLP_PROGRAM_CACHE_MAX:
        _MLP_PROGRAM_CACHE.popitem(last=False)
    return prog


def mlp_apply(params, x: jax.Array, spec: MLPSpec, *,
              weight_bits: int = 8, act_bits: int = 8,
              mode: str = "float",
              programmed: Optional[ProgrammedMLP] = None,
              use_kernel: bool = False) -> jax.Array:
    """mode: float | qat | crossbar | digital — the Fig. 12 sweep axes.

    float/qat are the ex-situ TRAINING forward (the QAT trainer's path).
    The deployed modes are DEPRECATED here: crossbar/digital execution
    belongs to the chip API — ``repro.chip.compile_chip(spec,
    params=...).stream(x)`` — which also maps/routes the network. This
    shim keeps old call sites working: pass ``programmed`` (from
    program_mlp) explicitly, or let the built-in memo program this
    param set on first use — repeated calls never re-encode either way."""
    if mode in ("crossbar", "digital"):
        if programmed is None:
            _deprecated(f"mlp_apply(mode={mode!r})",
                        "repro.chip.compile_chip(spec, params=...)"
                        ".stream(x)")
            programmed = _cached_program_mlp(params, spec, mode,
                                             weight_bits)
        return programmed_mlp_apply(programmed, x, use_kernel=use_kernel)

    h = x
    n = len(params)
    for i, p in enumerate(params):
        act = spec.activation if i < n - 1 else spec.out_activation
        if mode == "qat":
            w = q.fake_quant(p["w"], bits=weight_bits, per_column=True)
            h = h @ w + p["b"]
            h = q.make_activation(act)(h)
            if i < n - 1:
                h = q.fake_quant_act(h, bits=act_bits)
        else:
            h = h @ p["w"] + p["b"]
            h = q.make_activation(act)(h)
    return h
