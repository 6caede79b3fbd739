"""Pallas TPU kernel: tiled differential-pair crossbar MVM (Eq. 3),
program-once / stream-many edition.

Hardware adaptation (DESIGN.md §2): the paper's analog crossbar
evaluates a whole weight-stationary tile in one step; the TPU-native
equivalent is an MXU pass over a VMEM-resident tile. The paper's split
between *programming* (slow, once) and *streaming evaluation* (fast,
millions of times) is mirrored exactly:

  program time (core/crossbar_layer.program_layer):
    - tile + differential-encode the weights,
    - fold Eq. 3's divider Σ(σ⁺+σ⁻), the per-tile weight scale and the
      wire-attenuation correction into ONE per-tile-column `scale`.
  evaluate time (this kernel — the streaming hot path):
    1. differential combine   w = σ⁺ − σ⁻          (VPU, elementwise)
    2. dot product            num = x @ w           (MXU)
    3. folded rescale         acc += num · scale    (VPU, one FMA)
    4. epilogue (last chunk)  out = act(acc + bias) (VPU, fused)

The input-independent divider is *not* recomputed per inference — that
is the whole point of Eq. 3's observation that the column gain depends
only on the programmed state. The kernel inner loop is therefore pure
MXU work plus two vector FMAs, and bias + activation never round-trip
to HBM.

Grid = (B-blocks, column-tiles, row-chunks); the row-chunk axis is the
reduction (Fig. 11 combining) and runs innermost, accumulating into the
output block, which stays resident in VMEM across the reduction
("revisiting" schedule). The first two grid axes are declared
`parallel` (dimension_semantics) so Mosaic may reorder/parallelize
them; only the reduction is `arbitrary`. Tile geometry mirrors the
paper's crossbar cores: rows=128 is MXU-aligned; cols=64 is the paper's
geometry (the beyond-paper 128×128 geometry fills MXU lanes).

An optional bf16 input path casts the combined tile to bf16 so the MXU
pass runs at bf16×bf16→f32 throughput; accumulation stays f32.

VMEM budget per step (f32): x (Bt·rows) + gp,gn (2·rows·cols) + out
(Bt·cols) ≈ 4·(128·128·3) B ≈ 200 KiB at Bt=128 — comfortably inside
the ~16 MiB VMEM of a v5e core, leaving room for double-buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import ACTIVATIONS as _ACTIVATIONS


def _kernel(x_ref, gp_ref, gn_ref, scale_ref, bias_ref, o_ref, *,
            n_rowchunks: int, activation: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]            # (Bt, rows)
    w = gp_ref[...] - gn_ref[...]   # (rows, cols)
    if x.dtype == jnp.bfloat16:
        w = w.astype(jnp.bfloat16)
        precision = None
    else:
        # f32 operands: full-precision MXU passes, not one bf16 pass
        precision = jax.lax.Precision.HIGHEST
    num = jnp.dot(x, w, preferred_element_type=jnp.float32,
                  precision=precision)
    # scale: (1, cols) — folded descale/Σ(σ⁺+σ⁻)
    o_ref[...] += num * scale_ref[...]

    @pl.when(pl.program_id(2) == n_rowchunks - 1)
    def _epilogue():
        o_ref[...] = _ACTIVATIONS[activation](o_ref[...] + bias_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("activation", "block_b", "interpret"))
def crossbar_mvm(x: jax.Array, gp: jax.Array, gn: jax.Array,
                 scale: jax.Array, bias: jax.Array | None = None, *,
                 activation: str = "linear", block_b: int = 128,
                 interpret: bool = False) -> jax.Array:
    """x: (B, R, rows) f32/bf16; gp/gn: (R, C, rows, cols) f32;
    scale: (R, C, cols) f32 (program-time folded divider + descale);
    bias: (C*cols,) f32 or None → (B, C*cols) f32 = act(Σ_r x·w·s + b).
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unsupported fused activation: {activation!r}")
    B, R, rows = x.shape
    _, C, _, cols = gp.shape
    if bias is None:
        bias = jnp.zeros((C * cols,), jnp.float32)
    bt = min(block_b, B)
    pad_b = (-B) % bt
    if pad_b:
        # partial-block contents are unspecified in Pallas; keep the
        # batch dim an exact multiple so every read is in-bounds.
        x = jnp.pad(x, ((0, pad_b), (0, 0), (0, 0)))
    nb = x.shape[0] // bt

    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)
    flops = 2 * x.shape[0] * R * rows * C * cols + 2 * x.shape[0] * C * cols
    bytes_accessed = (x.size * x.dtype.itemsize + 2 * gp.size * 4 +
                      scale.size * 4 + bias.size * 4 +
                      x.shape[0] * C * cols * 4)
    transcendentals = (x.shape[0] * C * cols
                       if activation in ("sigmoid", "tanh") else 0)

    # Mosaic tiles the last two dims of every block by (8, 128) unless
    # the block spans the whole dim, so the row-chunk and column-tile
    # axes are kept out of the last two dims and squeezed: x is laid
    # out (R, B, rows), the per-column vectors (…, 1, cols), and the
    # output (C, B, cols).
    sq = pl.squeezed
    out = pl.pallas_call(
        functools.partial(_kernel, n_rowchunks=R, activation=activation),
        grid=(nb, C, R),
        in_specs=[
            pl.BlockSpec((sq, bt, rows), lambda b, c, r: (r, b, 0)),
            pl.BlockSpec((sq, sq, rows, cols), lambda b, c, r: (r, c, 0, 0)),
            pl.BlockSpec((sq, sq, rows, cols), lambda b, c, r: (r, c, 0, 0)),
            pl.BlockSpec((sq, sq, 1, cols), lambda b, c, r: (r, c, 0, 0)),
            pl.BlockSpec((sq, 1, cols), lambda b, c, r: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((sq, bt, cols), lambda b, c, r: (c, b, 0)),
        out_shape=jax.ShapeDtypeStruct((C, x.shape[0], cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=transcendentals),
        interpret=interpret,
    )(x.transpose(1, 0, 2), gp.astype(jnp.float32),
      gn.astype(jnp.float32),
      scale.astype(jnp.float32).reshape(R, C, 1, cols),
      bias.astype(jnp.float32).reshape(C, 1, cols))
    return out[:, :B].transpose(1, 0, 2).reshape(B, C * cols)
