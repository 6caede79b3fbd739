"""Pallas TPU kernel: the SRAM digital core's int8 MAC array (§II.A).

The digital core multiplies 8-bit synapses with 8-bit inputs into
32-bit accumulators, all neurons in parallel. On TPU that is an
int8×int8→int32 MXU pass; the kernel keeps a (K-blocked) int32
accumulator resident in VMEM, mirroring the core's accumulator bank.

Program-once / stream-many: the digital core's requantization
constants (weight scale, zero-point correction) are fixed when the
synapse SRAM is written, so `digital_linear`'s epilogue

    out = act(acc · scale + offset)        (scale/offset per neuron)

is fused into the final K-step of the kernel — one kernel call replaces
kernel + 4 jnp ops, and the int32 accumulator never round-trips to HBM.
Without `scale` the kernel returns the raw int32 accumulator (the bare
MAC-array datapath, used by the kernel-vs-oracle tests).

Grid = (B-blocks, N-blocks, K-blocks); K innermost (reduction), B/N
declared `parallel` for Mosaic. Block shapes default to MXU-native 128
tiles (a digital core *is* a 256×128 array — exactly two K-blocks by
one N-block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import ACTIVATIONS as _ACTIVATIONS


def _mac(x_ref, w_ref):
    # 8-bit operands straight into the MXU, int32 accumulation
    return jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _kernel_raw(x_ref, w_ref, zp_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.broadcast_to(zp_ref[...], o_ref.shape)

    o_ref[...] += _mac(x_ref, w_ref)


def _kernel_fused(x_ref, w_ref, zp_ref, scale_ref, offset_ref, o_ref,
                  acc_ref, *, n_kblocks: int, activation: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(zp_ref[...], acc_ref.shape)

    acc_ref[...] += _mac(x_ref, w_ref)

    @pl.when(pl.program_id(2) == n_kblocks - 1)
    def _epilogue():
        y = (acc_ref[...].astype(jnp.float32) * scale_ref[...] +
             offset_ref[...])
        o_ref[...] = _ACTIVATIONS[activation](y)


def _pad_dim(a: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)  # zero pad: contributes 0 to the MAC


@functools.partial(jax.jit,
                   static_argnames=("activation", "block_b", "block_n",
                                    "block_k", "interpret"))
def int8_matmul(x: jax.Array, w: jax.Array,
                scale: jax.Array | None = None,
                offset: jax.Array | None = None, *,
                activation: str = "linear", block_b: int = 128,
                block_n: int = 128, block_k: int = 256,
                interpret: bool = False) -> jax.Array:
    """x: (B, K) int8/uint8; w: (K, N) int8.

    scale is None  → (B, N) int32 raw accumulator.
    scale: (N,) f32 (offset: (N,) f32, default 0) →
        (B, N) f32 = act(acc·scale + offset), epilogue fused in-kernel.

    The MXU multiplies signed 8-bit operands only, so uint8 codes are
    shifted to int8 (x − 128) and the accumulator starts from the exact
    zero-point correction 128·Σ_k w[k, n] — the result is bit-identical
    to the unsigned product.
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unsupported fused activation: {activation!r}")
    if w.dtype != jnp.int8:
        raise TypeError(f"int8_matmul: w must be int8, got {w.dtype}")
    B, K = x.shape
    _, N = w.shape
    if x.dtype == jnp.uint8:
        x = (x.astype(jnp.int32) - 128).astype(jnp.int8)
        zp = 128 * jnp.sum(w.astype(jnp.int32), axis=0)
    elif x.dtype == jnp.int8:
        zp = jnp.zeros((N,), jnp.int32)
    else:
        raise TypeError(f"int8_matmul: x must be int8 or uint8, got "
                        f"{x.dtype}")
    bb, bn, bk = min(block_b, B), min(block_n, N), min(block_k, K)
    # pad every dim to a block multiple: partial-block contents are
    # unspecified in Pallas, and a ragged K reduction would otherwise
    # accumulate garbage.
    xp = _pad_dim(_pad_dim(x, 0, bb), 1, bk)
    wp = _pad_dim(_pad_dim(w, 0, bk), 1, bn)
    zpp = _pad_dim(zp.reshape(1, -1), 1, bn)
    grid = (xp.shape[0] // bb, wp.shape[1] // bn, xp.shape[1] // bk)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    cost = pl.CostEstimate(
        flops=2 * xp.shape[0] * xp.shape[1] * wp.shape[1],
        bytes_accessed=(xp.size + wp.size +
                        xp.shape[0] * wp.shape[1] * 4),
        transcendentals=(xp.shape[0] * wp.shape[1]
                         if activation in ("sigmoid", "tanh") else 0))
    operand_specs = [
        pl.BlockSpec((bb, bk), lambda b, n, k: (b, k)),
        pl.BlockSpec((bk, bn), lambda b, n, k: (k, n)),
        pl.BlockSpec((1, bn), lambda b, n, k: (0, n)),
    ]
    out_spec = pl.BlockSpec((bb, bn), lambda b, n, k: (b, n))

    if scale is None:
        out = pl.pallas_call(
            _kernel_raw,
            grid=grid,
            in_specs=operand_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                           jnp.int32),
            compiler_params=compiler_params,
            cost_estimate=cost,
            interpret=interpret,
        )(xp, wp, zpp)
        return out[:B, :N]

    if offset is None:
        offset = jnp.zeros((N,), jnp.float32)
    sp = _pad_dim(scale.astype(jnp.float32).reshape(1, -1), 1, bn)
    op = _pad_dim(offset.astype(jnp.float32).reshape(1, -1), 1, bn)
    out = pl.pallas_call(
        functools.partial(_kernel_fused, n_kblocks=grid[2],
                          activation=activation),
        grid=grid,
        in_specs=operand_specs + [
            pl.BlockSpec((1, bn), lambda b, n, k: (0, n)),
            pl.BlockSpec((1, bn), lambda b, n, k: (0, n)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.int32)],
        compiler_params=compiler_params,
        cost_estimate=cost,
        interpret=interpret,
    )(xp, wp, zpp, sp, op)
    return out[:B, :N]
