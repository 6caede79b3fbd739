"""Router accounting: one :class:`RouterStats` formula over the keyed
scheduler's counters and latency reservoirs.

The multi-app router's per-tenant rows and fleet roll-up
(:class:`repro.deploy.MultiAppRouter`), its cross-host gather
(:class:`repro.deploy.DistributedMultiAppRouter`) and the heartbeat-
board roll-up (:mod:`repro.fleet.ha`) all assemble their numbers here,
so lockstep, degraded-mode and per-app accounting can never drift
apart.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class RouterStats:
    """Roll-up of one router run (latencies over finished requests)."""
    requests: int
    items: int
    steps: int
    wall_s: float
    items_per_second: float
    occupancy: float                    # items / (steps × lanes)
    wait_s_mean: float                  # submit → lane admission
    latency_s_mean: float               # submit → last item
    latency_s_p50: float
    latency_s_p95: float
    rejected: int                       # submits refused (queue full)
    lanes: int = 0                      # slots behind these numbers

    def __str__(self) -> str:
        return (f"RouterStats[{self.requests} req / {self.items} items "
                f"in {self.steps} steps, {self.wall_s * 1e3:.1f} ms: "
                f"{self.items_per_second:.0f} items/s, occupancy "
                f"{self.occupancy:.0%}, latency p50 "
                f"{self.latency_s_p50 * 1e3:.1f} ms / p95 "
                f"{self.latency_s_p95 * 1e3:.1f} ms]")


def latency_arrays(finished):
    """Per-request (latency, wait) vectors over finished states — the
    one place the extraction idiom lives (stats, merges, gathers)."""
    lat = np.asarray([st.latency_s for st in finished]) \
        if finished else np.zeros((0,))
    wait = np.asarray([st.wait_s for st in finished]) \
        if finished else np.zeros((0,))
    return lat, wait


def stats_from_states(finished, *, items: int, steps: int, wall_s: float,
                      lanes: int, rejected: int,
                      lat_res=None, wait_res=None) -> RouterStats:
    """Assemble one :class:`RouterStats` from finished request states
    plus the engine counters — the one formula behind the multi-app
    router's per-tenant rows and its fleet roll-up (so per-app and
    fleet numbers can never drift apart).

    ``lat_res``/``wait_res`` (``repro.obs.Reservoir``) are the bounded
    accounting the keyed scheduler maintains per finish: means come
    from the reservoir's exact count/sum, percentiles from its
    retained samples — identical to the raw per-state lists for runs
    up to the reservoir size, bounded-memory after. Without them the
    historic extract-from-states path runs (exact, unbounded)."""
    if lat_res is not None and wait_res is not None:
        lat = lat_res.values
        return RouterStats(
            requests=len(finished),
            items=items,
            steps=steps,
            wall_s=wall_s,
            items_per_second=items / wall_s if wall_s else 0.0,
            occupancy=items / max(steps * lanes, 1),
            wait_s_mean=wait_res.mean,
            latency_s_mean=lat_res.mean,
            latency_s_p50=float(np.percentile(lat, 50))
            if lat.size else 0.0,
            latency_s_p95=float(np.percentile(lat, 95))
            if lat.size else 0.0,
            rejected=rejected,
            lanes=lanes,
        )
    lat, wait = latency_arrays(finished)
    return RouterStats(
        requests=len(finished),
        items=items,
        steps=steps,
        wall_s=wall_s,
        items_per_second=items / wall_s if wall_s else 0.0,
        occupancy=items / max(steps * lanes, 1),
        wait_s_mean=float(wait.mean()) if wait.size else 0.0,
        latency_s_mean=float(lat.mean()) if lat.size else 0.0,
        latency_s_p50=float(np.percentile(lat, 50)) if lat.size else 0.0,
        latency_s_p95=float(np.percentile(lat, 95)) if lat.size else 0.0,
        rejected=rejected,
        lanes=lanes,
    )


def merge_stats(stats: Sequence[RouterStats]) -> RouterStats:
    """Pure (no-communication) roll-up of per-host RouterStats.

    Counters (requests, items, rejected) add exactly; lanes add (the
    fleet's lanes are the hosts' disjoint lanes); steps and wall take
    the max (lockstep hosts step together, stragglers dominate wall);
    throughput is total items over the longest wall; occupancy is
    recomputed from the summed per-host lane-step products; latency
    means are request-weighted. Percentiles CANNOT be merged from
    percentiles — here they take the max across hosts (a conservative
    upper bound, exact when one host dominates). When the raw
    latencies are reachable, prefer
    :meth:`repro.deploy.DistributedMultiAppRouter.stats_global`, which
    gathers them and is exact.
    """
    if not stats:
        return RouterStats(0, 0, 0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                           0, 0)
    requests = sum(s.requests for s in stats)
    items = sum(s.items for s in stats)
    wall = max(s.wall_s for s in stats)
    lane_steps = sum(s.steps * s.lanes for s in stats)
    w = [s.requests for s in stats]
    wsum = sum(w) or 1
    return RouterStats(
        requests=requests,
        items=items,
        steps=max(s.steps for s in stats),
        wall_s=wall,
        items_per_second=items / wall if wall else 0.0,
        occupancy=items / lane_steps if lane_steps else 0.0,
        wait_s_mean=sum(s.wait_s_mean * n
                        for s, n in zip(stats, w)) / wsum,
        latency_s_mean=sum(s.latency_s_mean * n
                           for s, n in zip(stats, w)) / wsum,
        latency_s_p50=max(s.latency_s_p50 for s in stats),
        latency_s_p95=max(s.latency_s_p95 for s in stats),
        rejected=sum(s.rejected for s in stats),
        lanes=sum(s.lanes for s in stats),
    )


def assemble_stats(counts_all: np.ndarray, walls_all: np.ndarray,
                   lat_all: np.ndarray,
                   wait_all: np.ndarray) -> RouterStats:
    """The exact fleet-wide roll-up FORMULA, independent of how the
    per-host rows got here: ``counts_all`` is a (hosts, 5) int array
    of (requests, items, steps, rejected, lanes) rows, ``walls_all``
    the per-host wall clocks, ``lat_all``/``wait_all`` the
    concatenated per-request vectors. Shared by the collective gather
    (:func:`repro.deploy.router.gather_global_stats`) and the
    heartbeat-board roll-up (:mod:`repro.fleet.ha`), so lockstep and
    degraded-mode accounting can never drift apart."""
    counts_all = np.asarray(counts_all, np.int64).reshape(-1, 5)
    total_items = int(counts_all[:, 1].sum())
    lane_steps = int((counts_all[:, 2] * counts_all[:, 4]).sum())
    wall = float(np.asarray(walls_all).max()) if np.size(walls_all) \
        else 0.0
    lat_all = np.asarray(lat_all, np.float64).ravel()
    wait_all = np.asarray(wait_all, np.float64).ravel()
    return RouterStats(
        requests=int(counts_all[:, 0].sum()),
        items=total_items,
        steps=int(counts_all[:, 2].max()) if counts_all.size else 0,
        wall_s=wall,
        items_per_second=total_items / wall if wall else 0.0,
        occupancy=total_items / lane_steps if lane_steps else 0.0,
        wait_s_mean=float(wait_all.mean()) if wait_all.size else 0.0,
        latency_s_mean=float(lat_all.mean()) if lat_all.size else 0.0,
        latency_s_p50=float(np.percentile(lat_all, 50))
        if lat_all.size else 0.0,
        latency_s_p95=float(np.percentile(lat_all, 95))
        if lat_all.size else 0.0,
        rejected=int(counts_all[:, 3].sum()),
        lanes=int(counts_all[:, 4].sum()),
    )
