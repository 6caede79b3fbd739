"""The plain reference and the comparison that decides ``correct``.

The reference is a dense MLP in float32 under HIGHEST matmul
precision: ``h = act(h @ W + b)`` layer by layer, over the weights the
benchmark made (``bench.weights``) and the inputs it generated. It
knows nothing of tiles, conductances, combiners, quantizers or
meshes, and imports nothing of the program.

Hidden layers use the threshold activation (+1 where the
pre-activation is >= 0, else -1), so a rounding difference on a
pre-activation at zero flips a whole hidden unit. Rows whose reference
pre-activation lies within ``near_margin`` times the layer's largest
magnitude of zero are left out of the float comparison and counted;
every other row is compared. Two numbers are judged, both relative to
the largest reference magnitude on those rows: the largest absolute gap
(``rel_err``), and the root mean square gap (``rms_err``), which is
steadier from seed to seed and stands further from the control.

The control is the same reference one step lower in precision: float32
matmuls as three bfloat16 passes (``bf16x3``, what a TPU's HIGH
precision does, written out so that it means the same on any backend),
or the weights re-snapped onto an int4 grid (``int4``).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 4096


def _bf16_split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _matmul(a, b, mode: str):
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if mode == "highest":
        return mm(a, b)
    if mode == "high":          # the backend's own HIGH, for comparison
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if mode == "bf16x3":
        ah, al = _bf16_split(a)
        bh, bl = _bf16_split(b)
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown matmul mode {mode!r}")


def _act(kind: str, z):
    if kind == "threshold":
        return jnp.where(z >= 0, 1.0, -1.0).astype(jnp.float32)
    if kind == "linear":
        return z
    raise ValueError(f"unknown activation {kind!r}")


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _forward_block(params, x, acts: Tuple[str, ...], mode: str,
                   margin: float):
    h = x.astype(jnp.float32)
    near = jnp.zeros((x.shape[0],), bool)
    for p, act in zip(params, acts):
        z = _matmul(h, p["w"], mode) + p["b"]
        if act == "threshold":
            mag = jnp.abs(z)
            near = near | jnp.any(mag <= margin * jnp.max(mag), axis=1)
        h = _act(act, z)
    return h, near


def activations(cfg: dict) -> Tuple[str, ...]:
    n = len(cfg["dims"]) - 1
    a = cfg["activations"]
    return (a["hidden"],) * (n - 1) + (a["out"],)


def forward(cfg: dict, params: List[dict], x: np.ndarray,
            mode: str = "highest") -> Tuple[np.ndarray, np.ndarray]:
    """Outputs and the near-threshold mask of every row of ``x``,
    computed in blocks of rows so that a large pool fits. ``margin``
    is taken against each block's own largest pre-activation."""
    acts = activations(cfg)
    margin = float(cfg["check"]["near_margin"])
    ys, nears = [], []
    for i in range(0, x.shape[0], BLOCK_ROWS):
        y, near = _forward_block(params, jnp.asarray(x[i:i + BLOCK_ROWS]),
                                 acts, mode, margin)
        ys.append(np.asarray(y))
        nears.append(np.asarray(near))
    return np.concatenate(ys), np.concatenate(nears)


def errors(y: np.ndarray, ref: np.ndarray, near: np.ndarray) -> dict:
    """``rel_err`` and ``rms_err`` of ``y`` against ``ref`` over the rows
    away from every threshold; inf for a shape mismatch, a non-finite
    output, or nothing left to compare."""
    y = np.asarray(y, np.float32)
    keep = ~near
    if y.shape != ref.shape or not np.all(np.isfinite(y)) or \
            not keep.any():
        return {"rel_err": float("inf"), "rms_err": float("inf")}
    gap = (y[keep] - ref[keep]).astype(np.float64)
    scale = max(float(np.max(np.abs(ref[keep]))), 1e-30)
    return {"rel_err": float(np.max(np.abs(gap)) / scale),
            "rms_err": float(np.sqrt(np.mean(gap * gap)) / scale)}


def control_outputs(cfg: dict, params: List[dict],
                    x: np.ndarray) -> np.ndarray:
    """What the control puts in the program's place."""
    from bench import weights

    kind = cfg["check"]["control"]
    if kind == "bf16x3":
        return forward(cfg, params, x, mode="bf16x3")[0]
    if kind == "int4":
        rows, cols, _ = weights.grid_of(cfg)
        return forward(cfg, weights.resnap(params, rows, cols, 7), x)[0]
    raise ValueError(f"unknown control {kind!r}")
