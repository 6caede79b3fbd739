"""The sensor tenant's traffic generator: every sensor mix is a
parameter file it reads.

Two loops exist. ``"loop": "open"`` is independent senders: requests
arrive on a schedule fixed before the window, whatever the system
does, each a run of sensor items. ``"loop": "closed"`` is callers that
wait for each reply: back-to-back calls over a fixed set of batches.

Every input is an item of ``input_bits``-bit sensor codes mapped onto
[-1, 1] float32, drawn on the host from the seed before the window.
Sizes and gaps are stratified: every seed gets the same set of request
sizes and inter-arrival gaps (quantiles of the stated distributions),
in another order, so that a seed changes which work comes when, not
how much work there is.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one benchmark seed (any
    non-negative int, including ones wider than 64 bits)."""
    return np.random.default_rng([stream, int(seed)])


def sensor_items(gen: np.random.Generator, n: int, d: int,
                 bits: int) -> np.ndarray:
    """n items of d sensor codes in [0, 2^bits), as [-1, 1] float32."""
    top = 2 ** bits - 1
    codes = gen.integers(0, top + 1, (n, d), dtype=np.int32)
    return codes.astype(np.float32) * np.float32(2.0 / top) - \
        np.float32(1.0)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def item_counts(dist: dict, n: int) -> np.ndarray:
    """n request sizes: the stratified quantiles of the distribution."""
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    lo, hi = int(dist["min"]), int(dist["max"])
    x = np.exp(math.log(lo) + _quantiles(n) * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(arrivals: str, rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps in seconds at ``rate`` per second."""
    if arrivals != "poisson":
        raise ValueError(f"unknown arrival process {arrivals!r}")
    return -np.log1p(-_quantiles(n)) / rate


@dataclasses.dataclass(frozen=True)
class OpenSchedule:
    """Requests of an open-loop window: request i arrives ``t[i]``
    seconds after the window opens and carries pool rows
    ``start[i] : start[i] + n[i]``."""
    t: np.ndarray
    start: np.ndarray
    n: np.ndarray
    pool: np.ndarray

    @property
    def requests(self) -> int:
        return len(self.t)


def open_schedule(mix: dict, seed: int, seconds: float,
                  d_in: int) -> OpenSchedule:
    """The whole open-loop schedule of one window: a pure function of
    the mix, the seed, the window length and the item width. The rate
    is the cell's own (``cells/<cell>.json``); a mix states none."""
    if "rate_per_s" not in mix:
        raise ValueError("an open-loop cell states its offered rate "
                         "(rate_per_s) in cells/<cell>.json")
    rate = float(mix["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    order = rng(seed, 1)
    g = order.permutation(gaps(mix["arrivals"], rate, count))
    t = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    n = order.permutation(item_counts(mix["items_per_request"], count))
    pool_n = int(mix["pool_items"])
    start = rng(seed, 2).integers(0, pool_n - n + 1)
    pool = sensor_items(rng(seed, 3), pool_n, d_in, int(mix["input_bits"]))
    return OpenSchedule(t, start, n, pool)


def closed_batches(mix: dict, seed: int, d_in: int) -> np.ndarray:
    """(batches, items_per_call, d_in): the closed loop cycles through
    these host batches."""
    b, k = int(mix["batches"]), int(mix["items_per_call"])
    pool = sensor_items(rng(seed, 3), b * k, d_in, int(mix["input_bits"]))
    return pool.reshape(b, k, d_in)
