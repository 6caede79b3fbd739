"""The continuous-batching router — the one serving engine.

Every tenant owns a block of lanes (tagged by app name), and each
engine step dispatches each app's ``(lanes_app, d_in_app)`` batch to
THAT app's member — a :class:`repro.fleet.ShardedChip` whose programmed
plan is placed once on the one shared ``"chip"`` mesh, or an LM tenant
(:class:`repro.lm.LMMember`) — so one batched step per app per engine
step runs the whole multi-tenant fleet with zero re-programming
traffic. Slots backfill between steps (arriving requests drop into
lanes the moment one frees, never stalling resident streams), per-app
bounded queues give admission control for upstream backpressure, and
every request carries its latency accounting (submit → admit → first
item → done, in seconds and engine steps). ``serve(sources)`` is the
closed loop the paper's I/O model assumes: sensor-stream frontends
(:mod:`repro.fleet.source`) pump windowed items under backpressure
while the router streams the active set.

:class:`DistributedMultiAppRouter` is the SPMD shape: on a
``jax.distributed`` fleet no host can address the other hosts' chips,
so every process routes its own chips' lanes for EVERY app, in
lockstep — each app's batched step is a collective, so the per-step
dispatch schedule is pinned (every app, declaration order, idle or
not), and the serve/stop decision and the stats roll-up reduce across
hosts (the cross-host gathers at the bottom of this module).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from repro.serving.engine import (ItemRequest, KeyedItemStreamScheduler,
                                  StreamSpec)
from repro.serving.stats import (RouterStats, assemble_stats,
                                 stats_from_states)


@dataclasses.dataclass(frozen=True)
class DeploymentStats:
    """Per-app rows plus the fleet-wide roll-up, from the same engine
    counters — the per-app requests/items/rejected/lanes sum EXACTLY
    to the fleet row by construction (asserted in the selftest).

    ``variability`` (set by ``Deployment.stats`` when accuracy
    monitors / recalibrators are attached) carries the per-app canary
    accuracy series and recalibration events — the non-ideal-device
    observability plane next to the throughput counters."""
    apps: Dict[str, RouterStats]
    fleet: RouterStats
    variability: Optional[Dict[str, Any]] = None

    def __str__(self) -> str:
        lines = [f"  {name:>12s}: {stats}"
                 for name, stats in self.apps.items()]
        for name, entry in (self.variability or {}).items():
            monitor = entry.get("monitor") or {}
            recal = entry.get("recalibration") or {}
            lines.append(
                f"  {name:>12s}: canary_acc="
                f"{monitor.get('latest_accuracy')} "
                f"recals={recal.get('recals', 0)}")
        return "\n".join([f"DeploymentStats[fleet: {self.fleet}]"]
                         + lines)


class MultiAppRouter(KeyedItemStreamScheduler):
    """Keyed StreamingEngine over ``{app: member}`` fleet members that
    share one mesh (each member: a :class:`repro.fleet.ShardedChip`,
    or anything with ``stream_host(batch)``/``d_in``).

    ``lanes``/``queue_limits`` are per-app budgets. Requests must carry
    ``key=app_name`` (:meth:`submit_app` stamps it).

    The first step starts the wall clock, every step moves the
    last-step stamp, and ``_wall_s`` is the span the throughput and
    occupancy numbers divide by. High-availability instrumentation
    (:mod:`repro.fleet.ha`) attaches here: with a guard attached, every
    engine step is wrapped by :meth:`StepGuard.run_step` — a heartbeat
    published BEFORE entering the (possibly collective) step, a
    step-deadline check of the peers, and translation of a failed
    collective into :class:`repro.fleet.ha.MembershipChange` after the
    detector's bounded retry/backoff confirms who died.
    """

    _t_start: Optional[float] = None
    _t_last: float = 0.0
    _ha_guard = None
    _step_listeners: tuple = ()
    # True on the SPMD variant; degrade_to_local flips it off
    _spmd_lockstep = False

    def __init__(self, members: Mapping[str, Any], *,
                 lanes: Mapping[str, int],
                 queue_limits: Optional[Mapping[str, Optional[int]]] = None,
                 use_kernel: bool = False,
                 step_when_idle: bool = False):
        if not members:
            raise ValueError("MultiAppRouter needs at least one member")
        queue_limits = queue_limits or {}
        streams = {}
        for name, member in members.items():
            chip = getattr(member, "chip", member)
            if getattr(chip, "plan", 1) is None:
                raise ValueError(
                    f"app {name!r} is analytic-only (compiled without "
                    "weights): report() works, but it cannot join a "
                    "streaming router")
            streams[name] = StreamSpec(member.d_in, lanes[name],
                                       queue_limits.get(name))
        super().__init__(streams, step_when_idle=step_when_idle)
        self.members = dict(members)
        self.use_kernel = use_kernel
        self._uid = 0

    # ---------------- timed, guarded step --------------------------- #
    def attach_ha(self, guard) -> None:
        """Attach a :class:`repro.fleet.ha.StepGuard` (heartbeat +
        step-deadline failure detection around every engine step)."""
        self._ha_guard = guard

    def add_step_listener(self, fn) -> None:
        """Register ``fn(router)`` to run after every completed engine
        step — the observability hook ``repro.variability`` uses for
        canary scoring and closed-loop recalibration. Listeners run on
        the engine thread between steps (the only point where a live
        reprogram is safe) and their exceptions propagate: a failing
        monitor is a serving failure, not a silent skip."""
        self._step_listeners = (*self._step_listeners, fn)

    def step(self) -> int:
        if self._t_start is None:
            self._t_start = time.perf_counter()
        step_fn = super().step
        emitted = step_fn() if self._ha_guard is None \
            else self._ha_guard.run_step(step_fn)
        self._t_last = time.perf_counter()
        for fn in self._step_listeners:
            fn(self)
        return emitted

    def _wall_s(self) -> float:
        return (self._t_last - self._t_start) \
            if self._t_start is not None else 0.0

    # ---------------- payload ------------------------------------- #
    # The member's host-to-host verb: a jax-array return would add a
    # device round-trip per engine step. The distributed variant
    # streams each rank's own rows (``stream_local``); degraded mode
    # flips it back off on the instance.
    _local_stream = False

    def _stream_batch_key(self, key, batch: np.ndarray) -> np.ndarray:
        member = self.members[key]
        if self._local_stream:
            return member.stream_local(batch, use_kernel=self.use_kernel)
        return member.stream_host(batch, use_kernel=self.use_kernel)

    # ---------------- member lane-lifecycle hooks ------------------- #
    # Members that bind per-lane device state to the lane lifecycle
    # (the LM tenant's KV-cache slots: ``repro.lm.LMMember``) expose
    # ``on_admit(lane, state)`` / ``on_release(lane)``; lanes are
    # member-relative (slot minus the app's block base). Sensor members
    # expose neither and pay one getattr per lifecycle event.
    def _notify_admit(self, slot: int, st) -> None:
        key = self._slot_key[slot]
        hook = getattr(self.members[key], "on_admit", None)
        if hook is not None:
            hook(slot - self._base[key], st)

    def _begin(self, req, slot):
        st = super()._begin(req, slot)
        self._notify_admit(slot, st)
        return st

    def _resume(self, st, slot):
        st = super()._resume(st, slot)
        self._notify_admit(slot, st)
        return st

    def _release(self, st) -> None:
        key = self._slot_key[st.slot]
        hook = getattr(self.members[key], "on_release", None)
        if hook is not None:
            hook(st.slot - self._base[key])
        super()._release(st)

    # ---------------- submission ----------------------------------- #
    def submit_app(self, app: str, items) -> Optional[ItemRequest]:
        """Wrap ``items`` into a request tagged for ``app`` and submit
        it; returns the request, or None if the app's admission queue
        refused it (backpressure). A pre-built :class:`ItemRequest`
        (e.g. an LM decode request from :func:`repro.lm.lm_request`)
        is submitted as-is, with its uid/key stamped here."""
        if app not in self.members:
            raise ValueError(f"unknown app {app!r} (deployed: "
                             f"{sorted(self.members)})")
        if isinstance(items, ItemRequest):
            req = items
            req.uid, req.key = self._uid, app
        else:
            req = ItemRequest(uid=self._uid, items=items, key=app)
        self._uid += 1
        return req if self.submit(req) else None

    # ---------------- the closed serving loop ---------------------- #
    def serve(self, sources: Mapping[str, Any], *,
              max_steps: int = 100_000) -> List:
        """Drain one bounded source per app under backpressure: pump
        every source, admit as much as each app's admission queue
        accepts (rejected requests stay queued at their source, un-
        dropped), then run one keyed engine step — or skip/stop, per
        :meth:`_serve_decision` (the one point the distributed
        lockstep variant overrides); stop when nothing is queued,
        active or un-pumped anywhere. ``max_steps`` bounds loop
        ITERATIONS, so the loop terminates even if admission never
        makes progress. Returns the finished states (all apps; each
        state's ``request.key`` says whose)."""
        unknown = set(sources) - set(self.members)
        if unknown:
            raise ValueError(f"serve: sources for unknown apps "
                             f"{sorted(unknown)}")
        for name in sources:
            limit = self._streams[name].queue_limit
            if limit is not None and limit < 1:
                raise ValueError(
                    f"serve: app {name!r} has queue_limit 0 — a "
                    "zero-capacity admission queue can never admit a "
                    "request, so the serve loop could not make "
                    "progress")
        for _ in range(max_steps):
            for name, src in sources.items():
                src.pump()
                while True:
                    req = src.peek()
                    if req is None:
                        break
                    if req.key is None:
                        req.key = name
                    elif req.key != name:
                        raise ValueError(
                            f"serve: source for app {name!r} produced "
                            f"a request tagged {req.key!r}")
                    if not self.submit(req):
                        break
                    src.take()
            decision = self._serve_decision(sources)
            if decision == "stop":
                break
            if decision == "step":
                self.step()
        return self.finished

    def _serve_decision(self, sources) -> str:
        if self.queue or self.active:
            return "step"
        if all(src.exhausted for src in sources.values()):
            return "stop"
        for src in sources.values():
            src.pump()
        if all(src.peek() is None for src in sources.values()):
            return "stop"               # sources dry, nothing queued
        return "skip"

    # ---------------- elastic resize ------------------------------- #
    def resize_lanes(self, lanes: Mapping[str, int]) -> None:
        """Live per-app lane-budget change — what
        :meth:`repro.deploy.Deployment.resize` drives after remeshing
        the members: every app's lane block is rebuilt to its new
        budget, in-flight lanes are evicted and requeued at the front
        (no drop, no dup, no re-streaming — progress is preserved),
        and all counters carry over. Apps missing from ``lanes`` keep
        their current budget; queue limits are untouched."""
        streams = {
            name: StreamSpec(spec.d_in, lanes.get(name, spec.lanes),
                             spec.queue_limit)
            for name, spec in self._streams.items()}
        self.resize_streams(streams)

    # ---------------- observability -------------------------------- #
    def _obs_tags(self):
        return {"router": type(self).__name__,
                "apps": ",".join(map(str, self.members)),
                "lanes": self.slots}

    # ---------------- accounting ----------------------------------- #
    def _latency_arrays(self):
        """Bounded per-request (latency, wait) vectors — the
        scheduler's finish-time reservoirs, NOT re-extracted from the
        unbounded finished-state list (exact for runs up to the
        reservoir size; what the HA board publishes, so its size is
        bounded too)."""
        return self._lat_all.values, self._wait_all.values

    def _finished_for(self, app: str) -> list:
        return [st for st in self.finished if st.request.key == app]

    def stats_app(self, app: str) -> RouterStats:
        """One tenant's row (lanes/occupancy against ITS budget);
        latency percentiles ride the app's bounded reservoir (exact
        for runs up to the reservoir size)."""
        return stats_from_states(self._finished_for(app),
                                 items=self.items_by_key[app],
                                 steps=self.steps,
                                 wall_s=self._wall_s(),
                                 lanes=self._streams[app].lanes,
                                 rejected=self.rejected_by_key[app],
                                 lat_res=self._lat_by_key[app],
                                 wait_res=self._wait_by_key[app])

    def stats(self) -> DeploymentStats:
        fleet = stats_from_states(self.finished,
                                  items=self.items_emitted,
                                  steps=self.steps,
                                  wall_s=self._wall_s(),
                                  lanes=self.slots,
                                  rejected=self.rejected,
                                  lat_res=self._lat_all,
                                  wait_res=self._wait_all)
        return DeploymentStats(
            apps={name: self.stats_app(name) for name in self.members},
            fleet=fleet)


class DistributedMultiAppRouter(MultiAppRouter):
    """The multi-app router's SPMD-lockstep shape (see module doc).

    Every process of the ``jax.distributed`` job constructs one over
    the same members (whose shared mesh spans the processes) and drives
    it with the same call sequence. Each process schedules only its
    local chips' lanes and feeds them from its own source; request
    payloads and results never leave the host that owns them.
    ``step_when_idle`` is forced on — per-app batched steps are
    collectives in declaration order, and an idle rank skipping one
    would deadlock the ranks still serving that app. The drain and
    serve loops replace their local "anything left?" tests with an
    all-hosts OR, so every rank executes the same number of collective
    steps and breaks on the same iteration.

    ``_spmd_lockstep`` is the degraded-mode switch: after a membership
    change (:func:`repro.fleet.ha.degrade_to_local` flips it False on
    the instance) the surviving rank can no longer join collectives
    with the dead peers, so every cross-host reduction falls back to
    its local value and the router behaves like its single-process
    parent — same lanes, same counters, same accounting.
    """

    _spmd_lockstep = True

    def __init__(self, members, *, lanes, queue_limits=None,
                 use_kernel: bool = False, step_when_idle: bool = True):
        if not step_when_idle:
            raise ValueError(
                "DistributedMultiAppRouter always steps when idle: "
                "every app's batched step is a collective, and a "
                "locally idle rank that skipped one would deadlock "
                "the ranks that still have traffic")
        for name, member in members.items():
            if not getattr(member, "is_distributed", False):
                raise ValueError(
                    f"app {name!r}: member's mesh does not span "
                    "processes; on one process use MultiAppRouter")
        super().__init__(members, lanes=lanes, queue_limits=queue_limits,
                         use_kernel=use_kernel, step_when_idle=True)

    # (local lanes, d_in) → (local lanes, d_out): each rank
    # contributes its lanes' rows and reads back its own shards
    _local_stream = True

    # ---------------- lockstep control plane ----------------------- #
    def _any_across_hosts(self, flag: bool) -> bool:
        if not self._spmd_lockstep:
            return bool(flag)
        if self._ha_guard is not None:
            return self._ha_guard.call(any_across_hosts, flag)
        return any_across_hosts(flag)

    def run_until_drained(self, max_steps: int = 10_000) -> List:
        steps = 0
        while steps < max_steps:
            if not self._any_across_hosts(
                    bool(self.queue or self.active)):
                break
            self.step()
            steps += 1
        return self.finished

    def _serve_decision(self, sources) -> str:
        if not self._spmd_lockstep:
            return MultiAppRouter._serve_decision(self, sources)
        more = bool(self.queue or self.active or
                    not all(s.exhausted for s in sources.values()))
        return "step" if self._any_across_hosts(more) else "stop"

    def stats_global(self) -> DeploymentStats:
        """Exact fleet-wide per-app + roll-up stats (collective: every
        rank must call together; any rank can report the result — no
        host-0 pinning). Each app's counters and raw latencies gather
        separately, in declaration order, then the fleet row gathers
        the totals — percentiles are computed over every finished
        request in the fleet, never merged from per-host percentiles.
        In degraded mode (after a membership change) collectives with
        the dead peers are impossible, so this returns the LOCAL stats
        — use the heartbeat-board roll-up for the cross-survivor
        view."""
        import jax

        if not self._spmd_lockstep or jax.process_count() == 1:
            return self.stats()
        wall = self._wall_s()
        apps = {}
        for name in self.members:
            fin = self._finished_for(name)
            apps[name] = gather_global_stats(
                self._lat_by_key[name].values,
                self._wait_by_key[name].values, requests=len(fin),
                items=self.items_by_key[name], steps=self.steps,
                rejected=self.rejected_by_key[name],
                lanes=self._streams[name].lanes, wall_s=wall)
        fleet = gather_global_stats(
            self._lat_all.values, self._wait_all.values,
            requests=len(self.finished),
            items=self.items_emitted, steps=self.steps,
            rejected=self.rejected, lanes=self.slots, wall_s=wall)
        return DeploymentStats(apps=apps, fleet=fleet)

    def _obs_tags(self):
        import jax

        tags = MultiAppRouter._obs_tags(self)
        tags["host"] = jax.process_index()
        return tags

    def metrics_global(self) -> dict:
        """Fleet-wide merge of every rank's ``repro.obs`` registry
        snapshot (collective while in lockstep; degraded mode falls
        back to the local snapshot) — what
        :meth:`repro.deploy.Deployment.metrics` serves on a
        distributed deployment."""
        import jax

        from repro.obs import current, merge_snapshots
        from repro.obs.dist import allgather_snapshots

        snap = current().metrics.snapshot()
        if not self._spmd_lockstep or jax.process_count() == 1:
            return snap
        return merge_snapshots(allgather_snapshots(snap))


# ------------------------------------------------------------------- #
# cross-host primitives (the distributed router's control plane)
# ------------------------------------------------------------------- #
def any_across_hosts(flag: bool) -> bool:
    """OR-reduce a python bool over all hosts (one tiny gloo
    allgather; every rank must call this together)."""
    import jax

    if jax.process_count() == 1:
        return bool(flag)
    from jax.experimental import multihost_utils
    flags = multihost_utils.process_allgather(
        np.asarray([1 if flag else 0], np.int32))
    return bool(np.asarray(flags).sum() > 0)


def allgather_i64(counts: np.ndarray) -> np.ndarray:
    """Allgather a (n,) int64 counter vector → (hosts, n).

    int32 on the wire: the default CPU client is x32 (an int64 input
    would be silently downcast), so counters ride as (hi, lo) int32
    halves — a long-lived fleet, days at the benchmarked items/s,
    cannot overflow the gather."""
    from jax.experimental import multihost_utils

    counts = np.asarray(counts, np.int64)
    halves = np.stack([counts >> 31,
                       counts & 0x7FFFFFFF]).astype(np.int32)
    halves_all = np.asarray(
        multihost_utils.process_allgather(halves)).astype(np.int64)
    return (halves_all[:, 0, :] << 31) | halves_all[:, 1, :]


def allgather_latencies(lat: np.ndarray, wait: np.ndarray,
                        n_max: int):
    """Allgather per-request latency/wait vectors, NaN-padded to the
    fleet-wide max request count ``n_max`` (float32 on the wire keeps
    ~0.1 µs resolution on second-scale latencies). Returns the
    concatenated fleet-wide (lat, wait) with padding stripped."""
    from jax.experimental import multihost_utils

    pad = np.full((2, n_max), np.nan, np.float32)
    pad[0, :lat.size] = lat
    pad[1, :wait.size] = wait
    gathered = np.asarray(multihost_utils.process_allgather(pad)) \
        if n_max else np.zeros((1, 2, 0))
    lat_all = gathered[:, 0, :].ravel()
    wait_all = gathered[:, 1, :].ravel()
    return lat_all[~np.isnan(lat_all)], wait_all[~np.isnan(wait_all)]


def gather_global_stats(lat: np.ndarray, wait: np.ndarray, *,
                        requests: int, items: int, steps: int,
                        rejected: int, lanes: int,
                        wall_s: float) -> RouterStats:
    """Assemble the exact cross-host :class:`RouterStats` for one
    stream's local numbers (collective: every rank must call together,
    with the same sequence of streams)."""
    counts = np.asarray([requests, items, steps, rejected, lanes],
                        np.int64)
    counts_all = allgather_i64(counts)
    from jax.experimental import multihost_utils
    walls_all = np.asarray(multihost_utils.process_allgather(
        np.asarray([wall_s], np.float32)))

    # pad to the fleet-wide max VECTOR length, not the max request
    # count: the vectors are bounded reservoirs (repro.obs), so the
    # wire size stays bounded however long the serve ran
    sizes_all = allgather_i64(np.asarray([lat.size, wait.size],
                                         np.int64))
    n_max = int(sizes_all.max())
    lat_all, wait_all = allgather_latencies(lat, wait, n_max)
    return assemble_stats(counts_all, walls_all, lat_all, wait_all)
