"""The work one stream call needs, from the published layer widths.

Counted from the configuration's widths, not from how the program
tiles them, so that the roofline reads the same work whatever
implements a layer (einsum or Pallas, folded or unfolded tiles).
For a call of B items on one chip:

  FLOPs = 2 * B * sum(d_in * d_out)
  bytes = B * d_in * 4            the float32 items as handed in
        + sum(d_in * d_out) * w   the effective weights, read once
        + B * d_out * 4           the float32 outputs

with ``w`` the configuration's ``weight_bytes`` (4 for float32
memristor tiles, 1 for int8 digital synapses).
"""
from __future__ import annotations

from typing import Sequence


def macs(dims: Sequence[int]) -> int:
    return sum(a * b for a, b in zip(dims, dims[1:]))


def flops(dims: Sequence[int], items: int) -> int:
    return 2 * items * macs(dims)


def bytes_moved(dims: Sequence[int], items: int, weight_bytes: int) -> int:
    return items * dims[0] * 4 + macs(dims) * weight_bytes + \
        items * dims[-1] * 4


def min_seconds(dims: Sequence[int], items: int, weight_bytes: int,
                peak_ops: float, hbm_bytes_per_s: float) -> float:
    """The least time one chip could take for a call of ``items``: the
    larger of the compute bound and the memory bound."""
    return max(flops(dims, items) / peak_ops,
               bytes_moved(dims, items, weight_bytes) / hbm_bytes_per_s)
