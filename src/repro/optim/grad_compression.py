"""Gradient compression with error feedback (distributed-optimization
trick for the scarce inter-pod links — DESIGN.md §5).

The DP gradient all-reduce is the only cross-pod traffic in the
production mesh. Compressing it int8 cuts wire bytes 4× (vs f32
accumulation) at the cost of quantization error, which error feedback
re-injects next step so the *sum over time* is unbiased:

    q_t   = Q(g_t + e_t)
    e_t+1 = (g_t + e_t) − D(q_t)
    update uses  allreduce(D(q_t))

Implementation notes: inside one jit, XLA owns the all-reduce, so the
compression is expressed as an explicit shard_map psum over the DP axes
with int16 wire dtype (int8 codes summed across ≤ 512 pods/hosts need
the headroom; the wire cost is 2 B/elem vs 4 B/elem — the roofline
parser picks the s16 operands up from the HLO). Per-leaf scales ride a
tiny f32 psum.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def quantize_leaf(g: jax.Array, bits: int = 8
                  ) -> Tuple[jax.Array, jax.Array]:
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / qmax
    codes = jnp.clip(jnp.round(g / scale), -qmax, qmax).astype(jnp.int8)
    return codes, scale


def dequantize_leaf(codes: jax.Array, scale: jax.Array) -> jax.Array:
    return codes.astype(jnp.float32) * scale


def init_error(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_decompress(grads, error, bits: int = 8):
    """Local quantize→dequantize with error feedback (the lossy part;
    the reduction itself is whatever the caller wraps around it)."""
    def one(g, e):
        t = g.astype(jnp.float32) + e
        codes, scale = quantize_leaf(t, bits)
        deq = dequantize_leaf(codes, scale)
        return deq, t - deq

    out = jax.tree.map(one, grads, error)
    deq = jax.tree.map(lambda t: t[0], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    new_e = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return deq, new_e


def compressed_psum(mesh, dp_axes: Tuple[str, ...], grads, error,
                    bits: int = 8):
    """shard_map DP all-reduce of int8 codes on an s16 wire.

    grads are assumed DP-replicated per shard (the usual data-parallel
    gradient); returns the mean over the DP axes plus new error state.
    """
    def body(g_tree, e_tree):
        def one(g, e):
            t = g.astype(jnp.float32) + e
            codes, scale = quantize_leaf(t, bits)
            wire = codes.astype(jnp.int16)          # 2 B/elem on the wire
            total = wire
            smax = scale
            for ax in dp_axes:
                total = jax.lax.psum(total, ax)
                smax = jax.lax.pmax(smax, ax)
            n = 1
            for ax in dp_axes:
                n *= jax.lax.axis_size(ax)
            mean = total.astype(jnp.float32) * smax / n
            return mean, t - dequantize_leaf(codes, scale)

        out = jax.tree.map(one, g_tree, e_tree)
        mean = jax.tree.map(lambda t: t[0], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        new_e = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return mean, new_e

    spec = jax.tree.map(lambda _: P(), grads)
    espec = jax.tree.map(lambda _: P(), error)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, espec),
                         out_specs=(spec, espec))(grads, error)
