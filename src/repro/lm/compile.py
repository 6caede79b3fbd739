"""compile_lm: transformer blocks on the crossbar fabric.

The paper's companion work (Hasan & Taha, arXiv:1603.07400) argues the
streaming multicore substrate generalizes from small classifiers to
deep-network compute. This module is that claim for language models:
every matmul of a transformer block — the GQA projections
wq/wk/wv/wo or the latent attention's wq/wkv_a/wkv_b/wo with the
per-head wkv_b halves of absorbed decode, the SwiGLU w1/w3/w2, a MoE
layer's router, shared experts and held experts — is programmed onto
tile grids through the SAME ``program_layer`` → ``StreamLayer``
pipeline that maps the sensor MLPs, while everything a crossbar cannot
express (rms-norm, rotary embedding, softmax attention, routing and
dispatch, residuals, KV-cache surgery) stays jitted host-graph glue
from ``models/`` via the ``project``/``mlp_fn`` hooks.

Exactness discipline
--------------------
LM linears are programmed in EXACT mode (``quantize=False``): the
differential-pair encoding with the per-tile-column fold scale is
value-preserving — ``(gp - gn) · scale`` recovers the weight up to
float rounding — and the Fig. 11 combiner neurons' all-ones encodings
decode to exactly 1.0 even quantized (conductance endpoints are exact
levels). One functional image therefore serves BOTH systems: memristor
and digital differ in tile geometry (so tiling, combiner-tree depth
and the whole cost model differ) but share the exact encoding, which
is what lets ``CompiledLM.prefill``/``decode`` match the dense
``models/transformer.py`` forward at rel ≤ 1e-6. (The int8 +
DAC-clipped ``program_digital`` path cannot hit that bound; 8-bit LM
inference on the digital image is future work, gated on a QAT story.)
Host glue is forced to float32 compute for the same reason — the
mapped tile-grid partials accumulate in f32, and bf16 glue would
dominate the comparison; for the same reason the glue's matmuls run at
HIGHEST precision (a TPU otherwise takes one bf16 pass).

Cost accounting
---------------
The per-layer linears double as ``(1, (d_in, d_out))`` net tuples
through the ordinary ``map_networks`` split→pack→place→route pass
(one analytic :class:`repro.chip.CompiledChip`), so an LM tenant
prices through ``fabric_cost``/``deployment_report`` exactly like a
sensor app — Tables II–VI composition over mixed sensor+LM fabrics.
``tokens_per_second`` plays the role of the sensor SLO: it sizes the
replica fan-out and is validated against the routed TDM schedule at
deploy scope. Routing is all-pairs over the fabric, so a tenant of
more than ``ROUTED_TILE_LIMIT`` tiles (a MoE model at published
widths: tens of thousands) is left unpriced.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.chip.compile import (CompiledChip, StreamLayer, _apply_stream_layer,
                                _ChipStatic, _default_geom, _layer_plan,
                                _static, compile_chip)
from repro.core.crossbar_layer import program_layer
from repro.core.device import DEFAULT_DEVICE, DeviceModel
from repro.core.neural_core import CoreGeometry
from repro.core.systems import normalize_system
from repro.models import attention as attn
from repro.models import model as model_lib
from repro.models import moe as moe_mod
from repro.models import transformer as tf
from repro.models.layers import act_fn, rms_norm
from repro.obs.core import current as _obs_current

# the crossbar-mappable linears of one dense (GQA) block, in dataflow
# order
LM_LINEARS: Tuple[str, ...] = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
# linears programmed as one tile image with a leading group axis (the
# latent attention's per-head wkv_b halves, a MoE layer's held experts)
GROUPED_LINEARS: Tuple[str, ...] = attn.MLA_HEAD_LINEARS + (
    "experts.w1", "experts.w3", "experts.w2")
# rows one mapped linear evaluates at a time: longer prefills run in
# blocks, so the (rows, row chunks, d_out) partials stay a few hundred
# MB at the published widths; a grouped linear vmaps as many groups as
# fit GROUP_ROWS and loops over the rest
ROW_BLOCK = 512
GROUP_ROWS = 2048
# the analytic cost chip routes every flow of the whole fabric, in
# time that grows as about the tiles' 2.5th power (latent attention's
# linears at 128x64 on one CPU host: 868 tiles 20.7 s, 1,224 tiles
# 48 s, 1,936 tiles 151 s; Moonlight's one-chip share, 60,432 tiles,
# would take days); above this many tiles (~30 s) the tenant is mapped
# and programmed but not priced (``CompiledLM.chip`` is None)
ROUTED_TILE_LIMIT = 1024


@dataclasses.dataclass(frozen=True)
class TransformerParams:
    """A model config plus its dense parameter pytree — what a trainer
    (or a checkpoint loader) hands :func:`compile_lm` instead of a
    fresh seeded init."""
    cfg: Any
    params: Any


def _layer_params(cfg, stack) -> List[Tuple[Any, bool]]:
    """Each layer's parameters and whether its MLP is a MoE layer."""
    def at(tree, i):
        return jax.tree.map(lambda x: x[i], tree)
    if cfg.family == "moe" and cfg.first_dense_layers:
        k = cfg.first_dense_layers
        return [(at(stack["dense"], i), False) for i in range(k)] + \
            [(at(stack["moe"], i - k), True)
             for i in range(k, cfg.num_layers)]
    moe = cfg.family == "moe"
    return [(at(stack, i), moe) for i in range(cfg.num_layers)]


def _block_linears(cfg, p_l, moe: bool) -> Dict[str, jax.Array]:
    """The block's matmuls by name: (d_in, d_out) matrices, and
    (groups, d_in, d_out) stacks for ``GROUPED_LINEARS``. Flattened out
    of the head layout; QKV biases are NOT folded in — ``attn_apply``
    adds them in the host glue, so the programmed tiles stay pure
    matmuls (a crossbar bias row would re-quantize them). The router's
    selection bias stays glue too."""
    a = p_l["attn"]
    if cfg.mla:
        out = attn.mla_linears(cfg, a)
    else:
        d, H = cfg.d_model, cfg.num_heads
        KH, dh = cfg.num_kv_heads, cfg.head_dim
        out = {"wq": a["wq"].reshape(d, H * dh),
               "wk": a["wk"].reshape(d, KH * dh),
               "wv": a["wv"].reshape(d, KH * dh),
               "wo": a["wo"].reshape(H * dh, d)}
    m = p_l["mlp"]
    if not moe:
        out.update(w1=m["w1"], w3=m["w3"], w2=m["w2"])
        return out
    out["router"] = m["router"]
    for name in ("w1", "w3", "w2"):
        out[f"experts.{name}"] = m[name]
        if cfg.num_shared_experts:
            out[f"shared.{name}"] = m["shared"][name]
    return out


@functools.lru_cache(maxsize=None)
def _programmer(geom: CoreGeometry, device: DeviceModel, grouped: bool):
    """One jitted program → plan pass per linear (vmapped over the
    group axis for a grouped linear): EXACT encoding, linear output,
    Fig. 11 combiner neurons programmed alongside."""
    def one(w):
        lp = program_layer(w, geom=geom, device=device, quantize=False)
        return _layer_plan(lp, jnp.zeros((w.shape[1],), jnp.float32),
                           "linear", device)
    return jax.jit(jax.vmap(one) if grouped else one)


# --------------------------------------------------------------------- #
# the mapped forward (host glue + tile-grid projections)
# --------------------------------------------------------------------- #
def _apply_rows(layer: StreamLayer, x: jax.Array,
                use_kernel: bool) -> jax.Array:
    """x (N, d_in) → (N, d_out) through one mapped linear, in blocks of
    ``ROW_BLOCK`` rows when N is a larger multiple of it."""
    N = x.shape[0]
    if N <= ROW_BLOCK or N % ROW_BLOCK:
        return _apply_stream_layer(layer, x, use_kernel)
    out = jax.lax.map(lambda xb: _apply_stream_layer(layer, xb, use_kernel),
                      x.reshape(N // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(N, -1)


def _apply_grouped(layers: StreamLayer, x: jax.Array,
                   use_kernel: bool) -> jax.Array:
    """x (G, N, d_in) → (G, N, d_out): group g through its own tile
    image, as many groups at once as ``GROUP_ROWS`` rows allow."""
    G, N = x.shape[0], x.shape[1]
    batch = max(1, min(G, GROUP_ROWS // max(N, 1)))
    return jax.lax.map(lambda lx: _apply_rows(lx[0], lx[1], use_kernel),
                       (layers, x), batch_size=batch)


def _projector(layer_plans: Dict[str, StreamLayer], use_kernel: bool):
    """``project(name, x)`` over the mapped linears: x (..., d_in) for
    a plain linear; (N, G, d_in) for a grouped one."""
    def project(name: str, x: jax.Array) -> jax.Array:
        lay = layer_plans[name]
        if name in GROUPED_LINEARS:
            out = _apply_grouped(lay, jnp.swapaxes(x, 0, 1), use_kernel)
            return jnp.swapaxes(out, 0, 1)
        lead = x.shape[:-1]
        out = _apply_rows(lay, x.reshape(-1, x.shape[-1]), use_kernel)
        return out.reshape(*lead, -1)
    return project


def _swiglu(project, prefix: str, act: str, x: jax.Array) -> jax.Array:
    h = act_fn(act)(project(prefix + "w1", x)) * project(prefix + "w3", x)
    return project(prefix + "w2", h)


def _mlp_fn(project, cfg):
    def mlp(p_mlp, x: jax.Array) -> jax.Array:
        return _swiglu(project, "", cfg.act, x)
    return mlp


def _moe_fn(layer_plans: Dict[str, StreamLayer], project, cfg,
            use_kernel: bool, stats: List[Dict[str, jax.Array]]):
    """This chip's share of a MoE layer on the fabric: the mapped
    router over every expert, ``moe.route`` and the drop-free
    ``moe.held_dispatch`` in glue, the held experts as one grouped tile
    image applied to the tokens dispatched to each, the shared experts
    as plain mapped linears. Each call appends its dispatch counts to
    ``stats``."""
    def mlp(p_mlp, x: jax.Array) -> jax.Array:
        B, S, d = x.shape
        xt = x.reshape(B * S, d)
        logits = project("router", xt)
        w, ids, _ = moe_mod.route(cfg, logits, p_mlp.get("router_bias"))
        order, gates, st = moe_mod.held_dispatch(cfg, w, ids)
        x_e = jnp.take(xt, order, axis=0)               # (Eh, T, d)

        def expert(name, z):
            return _apply_grouped(layer_plans[name], z, use_kernel)
        h = act_fn(cfg.act)(expert("experts.w1", x_e)) * \
            expert("experts.w3", x_e)
        y = moe_mod.held_combine(expert("experts.w2", h), order, gates,
                                 B * S)
        if cfg.num_shared_experts:
            y = y + _swiglu(project, "shared.", cfg.act, xt)
        stats.append(st)
        return y.reshape(B, S, d).astype(x.dtype)
    return mlp


def _lm_forward(clm: "CompiledLM", batch, mode: str, cache,
                use_kernel: bool):
    """``model.forward`` with the scan over layers unrolled into a
    python loop (each layer owns a distinct programmed tile image, so
    there is no stacked-parameter scan body to share) and the block's
    matmuls routed through ``_apply_stream_layer``. Positions, cache
    layout and everything else mirror the dense path exactly — the
    cache is bit-compatible with the dense engine's, which is what lets
    ``serving.kvcache`` slot surgery work unchanged. Decode writes each
    layer's new cache row in place into the whole (donated) cache.
    Returns (h, cache, counts, read): ``counts`` int32 (3,) sums the
    MoE layers' (assignments, rows, dropped), ``read`` int32 (2,) the
    latent-attention decodes' (slots read per lane, ring slots)."""
    cfg, params = clm.cfg, clm.params
    dtype = jnp.dtype(cfg.compute_dtype)
    h = model_lib._embed_in(cfg, params, batch, dtype)
    B, S = h.shape[0], h.shape[1]
    decode = mode == "decode"
    if decode:
        positions = batch["pos"].reshape(B, 1).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    windows = tf._layer_windows(cfg)
    caches, stats, reads = [], [], []
    for layer, (p_l, moe) in enumerate(_layer_params(cfg,
                                                     params["stack"])):
        plans = clm.plans[layer]
        project = _projector(plans, use_kernel)
        mlp = _moe_fn(plans, project, cfg, use_kernel, stats) if moe \
            else _mlp_fn(project, cfg)
        h, c_new, _ = tf._block_apply(
            p_l, cfg, h, positions=positions, mode=mode,
            cache=cache if decode else None, window=windows[layer],
            use_moe=moe, project=project, mlp_fn=mlp,
            layer=layer if decode else None, attn_stats=reads)
        if decode:
            cache = c_new
        else:
            caches.append(c_new)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if not decode:
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches) \
            if caches and caches[0] is not None else None
    counts = jnp.zeros((3,), jnp.int32)
    for st in stats:
        counts = counts + jnp.stack([st["assignments"], st["rows"],
                                     st["dropped"]])
    read = sum(reads, jnp.zeros((2,), jnp.int32))
    return h, cache, counts, read


@partial(jax.jit, static_argnames=("use_kernel",))
def _prefill(clm: "CompiledLM", tokens: jax.Array,
             use_kernel: bool = False):
    with jax.default_matmul_precision("highest"):
        h, cache, _, _ = _lm_forward(clm, {"tokens": tokens},
                                     "prefill", None, use_kernel)
        logits = model_lib._head(clm.cfg, clm.params,
                                 h[:, -1:, :])[:, 0, :]
    return logits, cache


@partial(jax.jit, static_argnames=("use_kernel",),
         donate_argnames=("cache",))
def _decode(clm: "CompiledLM", cache, tokens: jax.Array,
            pos: jax.Array, prev=None, fresh=None,
            use_kernel: bool = False):
    if prev is not None:
        tokens = jnp.where(fresh[:, None], tokens, prev[:, None])
    with jax.default_matmul_precision("highest"):
        h, new_cache, counts, read = _lm_forward(
            clm, {"tokens": tokens, "pos": pos}, "decode", cache,
            use_kernel)
        logits = model_lib._head(clm.cfg, clm.params, h)[:, 0, :]
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, counts, read, new_cache


# --------------------------------------------------------------------- #
# the compiled LM object
# --------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CompiledLM:
    """A transformer mapped onto the fabric (see module docstring).

    A jit-able pytree: the dense parameter tree (host glue: embeddings,
    norms, biases, LM head) and the per-layer programmed tile plans are
    the array leaves; the config, geometry and the analytic cost chip
    are static aux. ``prefill``/``decode`` mirror
    ``models.model.prefill``/``decode_step`` exactly — same signatures,
    same cache pytree — with the block matmuls running the mapped
    tile-grid path. ``decode_per_slot`` is always on (a CompiledLM
    exists to serve; lockstep callers pass per-lane positions)."""
    params: Any
    plans: Tuple[Dict[str, StreamLayer], ...]
    cfg: Any = _static()
    system: str = _static()
    geom: CoreGeometry = _static()
    tokens_per_second: float = _static()
    chip_static: _ChipStatic = _static()

    @property
    def chip(self) -> CompiledChip:
        """The analytic cost compile (map→route over the per-layer
        linears) — what ``deployment_report`` prices the tenant by;
        None above ``ROUTED_TILE_LIMIT`` tiles."""
        return self.chip_static.value

    @property
    def d_model(self) -> int:
        return self.cfg.d_model

    def init_cache(self, batch: int, cache_len: int,
                   dtype=jnp.bfloat16):
        return model_lib.init_cache(self.cfg, batch, cache_len, dtype)

    def prefill(self, tokens, *, use_kernel: bool = False):
        """tokens (B, S) int → (last-token logits (B, vocab), cache)."""
        toks = jnp.asarray(tokens, jnp.int32)
        if toks.ndim == 1:
            toks = toks[None, :]
        return _prefill(self, toks, use_kernel)

    def decode(self, cache, tokens, pos, *, use_kernel: bool = False):
        """tokens (B, 1) int, pos (B,) per-slot positions →
        (logits (B, vocab), new_cache)."""
        _, logits, _, new_cache = self.step(cache, tokens, pos,
                                            use_kernel=use_kernel)
        return logits, new_cache

    def step(self, cache, tokens, pos, *, use_kernel: bool = False):
        """One serving step: → (greedy next tokens (B,) int32, logits
        (B, vocab), MoE dispatch counts int32 (assignments, rows,
        dropped), new_cache), all left on the device."""
        nxt, logits, counts, _, new_cache = self.serve_step(
            cache, tokens, pos, use_kernel=use_kernel)
        return nxt, logits, counts, new_cache

    def serve_step(self, cache, tokens, pos, *, prev=None, fresh=None,
                   use_kernel: bool = False):
        """:meth:`step` with, before the cache, the latent attention's
        read: int32 (slots its branch read per lane, ring slots), summed
        over the layers; (0, 0) without latent attention.

        With ``prev`` (B,) int, a previous step's next tokens left on
        the device, and ``fresh`` (B,) bool, lane b decodes
        ``tokens[b]`` where ``fresh[b]`` and ``prev[b]`` elsewhere,
        merged inside the one jitted step."""
        if prev is not None:
            prev = jnp.asarray(prev, jnp.int32)
            fresh = jnp.asarray(fresh, bool)
        return _decode(self, cache, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(pos, jnp.int32), prev, fresh,
                       use_kernel)

    def report(self):
        if self.chip is None:
            raise ValueError(
                f"{self.cfg.name}: the fabric has more than "
                f"{ROUTED_TILE_LIMIT} tiles, too many for the analytic "
                "router; the tenant is programmed but not priced")
        return self.chip.report()


# --------------------------------------------------------------------- #
# the compile
# --------------------------------------------------------------------- #
def compile_lm(model, *, system: str = "memristor", geometry=None,
               tokens_per_second: float = 0.0, seed: int = 0,
               device: DeviceModel = DEFAULT_DEVICE) -> CompiledLM:
    """Map a transformer onto the fabric.

    ``model`` is a :class:`repro.configs.ModelConfig` (parameters are
    seeded deterministically from ``seed``) or a
    :class:`TransformerParams` carrying trained weights. ``geometry``
    pins the tile geometry as a ``(rows, cols)`` pair or
    :class:`CoreGeometry` (None → the system's paper optimum);
    ``tokens_per_second`` is the tenant SLO the analytic cost chip is
    replica-sized against (validated at deploy scope, like every other
    tenant's rate).

    Dense and MoE families map: GQA or latent attention, SwiGLU MLPs,
    a MoE layer's router, shared experts and held experts (one grouped
    tile image, evaluated over the drop-free dispatch). Each linear is
    programmed in one jitted pass, a grouped one vmapped over its group
    axis. The config's compute dtype is forced to float32 and
    ``decode_per_slot`` to True — the serving contract (see
    :class:`CompiledLM`); prefill and decode run their glue at HIGHEST
    matmul precision. SSM and hybrid families raise: their state scans
    have no static per-layer matmul set to program.
    """
    if isinstance(model, TransformerParams):
        cfg, params = model.cfg, model.params
    elif hasattr(model, "family") and hasattr(model, "num_layers"):
        cfg, params = model, None
    else:
        raise TypeError(
            f"compile_lm takes a ModelConfig or TransformerParams "
            f"(got {type(model).__name__}); MLPs/net tuples belong to "
            f"repro.chip.compile_chip")
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"compile_lm maps dense and MoE transformer blocks only; "
            f"family {cfg.family!r} (ssm/hybrid state scans have no "
            f"static per-layer matmul set to program)")
    system = normalize_system(system, context="compile_lm")
    cfg = cfg.replace(compute_dtype="float32", decode_per_slot=True)
    if params is None:
        params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    if geometry is None:
        geom = _default_geom(system)
    elif isinstance(geometry, CoreGeometry):
        geom = geometry
    else:
        geom = CoreGeometry(*geometry)

    plans = []
    nets = []
    for p_l, moe in _layer_params(cfg, params["stack"]):
        layer_plans = {}
        for name, w in _block_linears(cfg, p_l, moe).items():
            grouped = name in GROUPED_LINEARS
            layer_plans[name] = _programmer(geom, device, grouped)(
                w.astype(jnp.float32))
            nets.append((int(w.shape[0]) if grouped else 1,
                         (int(w.shape[-2]), int(w.shape[-1]))))
        plans.append(layer_plans)

    tiles = sum(n * math.ceil(a / geom.rows) * math.ceil(b / geom.cols)
                for n, (a, b) in nets)
    chip = None
    if tiles <= ROUTED_TILE_LIMIT:
        chip = compile_chip(tuple(nets), system=system, geom=geom,
                            items_per_second=tokens_per_second,
                            validate_rate=False)
    clm = CompiledLM(params=params, plans=tuple(plans), cfg=cfg,
                     system=system, geom=geom,
                     tokens_per_second=float(tokens_per_second),
                     chip_static=_ChipStatic(chip))
    tel = _obs_current()
    if tel.active:
        tel.metrics.counter("lm.compiles").inc()
    return clm
