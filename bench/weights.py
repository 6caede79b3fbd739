"""Weights of a configuration, made from the seed on the device.

The paper programs weights trained off-chip onto the cores (§III.D),
and weights trained for a core lie on its levels. So the benchmark's
random weights are drawn as ``normal(0, 1/fan_in)`` and then snapped
onto the configuration's weight grid: each group of ``rows`` x
``cols`` weights (a memristor tile, or a digital core's column) is
scaled by its largest magnitude and rounded to ``levels`` steps on
either side of zero. The program then encodes them without rounding
error, and the dense reference needs nothing of the program: the
snapped weights are the matrix it computes with.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import traffic


def _snap(w: jax.Array, rows: Optional[int], cols: int,
          levels: int) -> jax.Array:
    d_in, d_out = w.shape
    rows = rows or d_in
    R, C = math.ceil(d_in / rows), math.ceil(d_out / cols)
    wp = jnp.pad(w, ((0, R * rows - d_in), (0, C * cols - d_out)))
    t = wp.reshape(R, rows, C, cols)
    amax = jnp.max(jnp.abs(t), axis=(1, 3), keepdims=True)
    amax = jnp.maximum(amax, 1e-12)
    snapped = jnp.round(t / amax * levels) / levels * amax
    return snapped.reshape(R * rows, C * cols)[:d_in, :d_out]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, dims: Tuple[int, ...], grid: Tuple) -> List[dict]:
    rows, cols, levels = grid
    params = []
    for d_in, d_out in zip(dims, dims[1:]):
        key, k = jax.random.split(key)
        w = jax.random.normal(k, (d_in, d_out), jnp.float32) / \
            jnp.sqrt(jnp.float32(d_in))
        params.append({"w": _snap(w, rows, cols, levels),
                       "b": jnp.zeros((d_out,), jnp.float32)})
    return params


def grid_of(cfg: dict) -> Tuple:
    g = cfg["weights"]["grid"]
    return (g["rows"], g["cols"], g["levels"])


def make(cfg: dict, seed: int) -> List[dict]:
    """The configuration's weights for ``seed``: one jitted call,
    float32 on the default device."""
    key = jax.random.PRNGKey(int(traffic.rng(seed, 0).integers(0, 2**31)))
    return _make(key, tuple(cfg["dims"]), grid_of(cfg))


def resnap(params: List[dict], rows: Optional[int], cols: int,
           levels: int) -> List[dict]:
    """The same weights on a coarser grid (the int4 control)."""
    return [{"w": _snap(p["w"], rows, cols, levels), "b": p["b"]}
            for p in params]
