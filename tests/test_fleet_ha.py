"""Fault tolerance: failure detection, takeover, degraded serving.

Tier-1 layers (fast, no subprocess unless noted):

  * heartbeat board atomicity and the jax-free file convention shared
    with the chaos supervisor;
  * :class:`FailureDetector` under an injectable clock — step
    deadlines, bounded retry/backoff, clean-exit ("done") vs crash,
    start grace, collective-failure confirmation;
  * :class:`StepGuard` translating failed collectives into
    :class:`MembershipChange`;
  * (seed, step)-pure takeover: :func:`replay_requests` reconstructs a
    dead host's exact feed, minus the journaled uids;
  * source/scheduler re-admission: front-of-queue ``requeue`` that
    bypasses admission limits without double-charging backpressure;
  * :func:`degrade_to_local` on a router mid-serve: in-flight lanes
    evicted and requeued at the front, accounting carried over;
  * TWO interleaved :class:`HAFleetServer`s in ONE process over toy
    fleets — one is starved of ticks to simulate its death
    deterministically; the survivor must absorb its feed with EXACT
    accounting (replay and reject modes), and the board
    ``stats_global`` roll-up must cover the whole fleet from the
    surviving rank;
  * the chaos-capable supervisor itself (clean-exit vs crash, stderr
    tails, ``on_failure="continue"``, ``kill_at`` injection) driven by
    jax-free subprocess workers;
  * ``Deployment.resize`` under live traffic: zero compile passes,
    exact outputs (simulated-device subprocess).

Chaos layer (``--run-chaos`` / ``REPRO_RUN_CHAOS=1``): real worker
kills — the federated ``--chaos-selftest`` CLI, and the lockstep
``jax.distributed`` degrade path (kill a NON-coordinator rank
mid-collective; the survivor must catch :class:`MembershipChange`,
``degrade_to_local``, and finish both feeds).
"""
import os
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.deploy import MultiAppRouter
from repro.fleet.ha import (FailureDetector, HAConfig, HAFleetServer,
                            HeartbeatBoard, MembershipChange, StepGuard,
                            degrade_to_local, replay_requests,
                            source_snapshot)
from repro.fleet.source import BoundedQueue, StreamSource
from repro.launch import simdev
from repro.serving.engine import ItemRequest, ItemRequestState

D_IN = 3
APP = "app"


class ToyFleet:
    """Row-pure payload (y = 2x + 1): loss/duplication visible per
    item, no jax."""
    d_in = D_IN

    def __init__(self, n_chips=1):
        self.n_chips = n_chips

    def stream(self, x, use_kernel=False):
        return np.asarray(x, np.float32) * 2.0 + 1.0

    stream_host = stream

    def resize(self, n_chips=None, *, mesh=None):
        self.n_chips = n_chips if mesh is None else mesh.devices.size


def toy_router(*, lanes=2, queue_limit=None, **kw):
    """The one-app router over a 1-chip toy fleet."""
    return MultiAppRouter({APP: ToyFleet(1)}, lanes={APP: lanes},
                          queue_limits={APP: queue_limit}, **kw)


class ToyPipe:
    """(seed, step)-pure pipeline: any host can replay any step."""

    def batch(self, step):
        rng = np.random.default_rng(1000 + step)
        return rng.uniform(-1, 1, (2 + step % 3, D_IN)) \
            .astype(np.float32)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def make_detector(board, peers=(0, 1), rank=0, **cfg_kw):
    clock = FakeClock()
    cfg = HAConfig(**{"timeout_s": 2.0, "retries": 3,
                      "backoff_s": 0.25, **cfg_kw})
    det = FailureDetector(board, rank, peers, cfg,
                          clock=clock, sleep=clock.sleep)
    return det, clock


# ---------------------------------------------------------------------- #
# heartbeat board
# ---------------------------------------------------------------------- #
def test_board_publish_read_roundtrip(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    assert board.read(0) is None
    board.publish(0, {"rank": 0, "beat": 1, "step": 5,
                      "status": "serving"})
    got = board.read(0)
    assert got["beat"] == 1 and got["step"] == 5
    board.publish(0, {"rank": 0, "beat": 2, "step": 6,
                      "status": "serving"})
    assert board.read(0)["beat"] == 2       # replaced, not appended
    board.publish(3, {"rank": 3, "beat": 1})
    assert board.ranks() == [0, 3]


def test_board_convention_shared_with_supervisor(tmp_path):
    """The jax-free supervisor reads the same files the HA layer
    writes — one convention, two importers."""
    board = HeartbeatBoard(str(tmp_path))
    board.publish(1, {"rank": 1, "beat": 4, "step": 7,
                      "status": "serving"})
    via_simdev = simdev.read_board(str(tmp_path), 1)
    assert via_simdev == board.read(1)
    assert simdev.board_path(str(tmp_path), 1) == \
        str(tmp_path / "rank_1.json")


# ---------------------------------------------------------------------- #
# failure detector
# ---------------------------------------------------------------------- #
def test_detector_beating_peer_is_never_dead(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    for beat in range(1, 6):
        board.publish(1, {"rank": 1, "beat": beat, "status": "serving"})
        clock.t += 1.5                       # under the 2 s deadline
        assert det.poll() == set()
    assert det.dead == set() and det.alive == [0, 1]


def test_detector_stalled_peer_declared_after_deadline_and_retries(
        tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 3, "status": "serving"})
    assert det.poll() == set()
    clock.t += 1.9
    assert det.poll() == set()               # deadline not yet passed
    clock.t += 0.2
    t0 = clock.t
    assert det.poll() == {1}                 # stale + confirmed
    # the confirmation did spend the bounded retry/backoff budget
    assert clock.t - t0 == pytest.approx(0.25 + 0.5 + 1.0)
    assert det.dead == {1} and det.alive == [0]
    assert det.poll() == set()               # declared once, not again


def test_detector_beat_during_confirm_cancels_declaration(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 1, "status": "serving"})
    det.poll()
    clock.t += 5.0

    real_sleep = clock.sleep

    def sleep_and_revive(dt):                # the peer was merely slow
        real_sleep(dt)
        board.publish(1, {"rank": 1, "beat": 2, "status": "serving"})

    det._sleep = sleep_and_revive
    assert det.poll() == set()
    assert det.dead == set()


def test_detector_clean_exit_is_never_dead(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 9, "status": "done"})
    clock.t += 100.0                         # stale forever
    assert det.poll() == set()
    assert 1 in det.done and det.dead == set()


def test_detector_start_grace_covers_slow_boot(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board, start_grace_s=60.0)
    clock.t += 30.0                          # never published, in grace
    assert det.poll() == set()
    clock.t += 31.0                          # grace expired
    assert det.poll() == {1}


def test_detector_confirm_skips_the_deadline(tmp_path):
    """A failed collective means someone died NOW — confirm() runs the
    bounded retry sweep without waiting out the step deadline."""
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 1, "status": "serving"})
    det.poll()
    clock.t += 0.1                           # beat is FRESH
    assert det.poll() == set()
    assert det.confirm() == {1}              # but confirm declares


# ---------------------------------------------------------------------- #
# step guard
# ---------------------------------------------------------------------- #
def test_guard_beats_and_runs_the_step(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, _ = make_detector(board)
    beats = []
    guard = StepGuard(det, publish=lambda: beats.append(1))
    assert guard.run_step(lambda: 42) == 42
    assert beats == [1] and guard.steps_guarded == 1


def test_guard_translates_collective_failure_into_membership_change(
        tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 1, "status": "serving"})
    det.poll()
    clock.t += 0.1
    guard = StepGuard(det, publish=lambda: None)

    def failing_collective():
        raise RuntimeError("Connection reset by peer")

    with pytest.raises(MembershipChange) as exc:
        guard.run_step(failing_collective)
    assert exc.value.dead == [1]
    assert isinstance(exc.value.cause, RuntimeError)


def test_guard_reraises_when_no_peer_is_dead(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, _ = make_detector(board, peers=(0,))   # no peers at all
    guard = StepGuard(det, publish=lambda: None)
    with pytest.raises(ValueError, match="not a membership problem"):
        guard.run_step(lambda: (_ for _ in ()).throw(
            ValueError("not a membership problem")))


def test_guard_detects_stale_peer_before_entering_the_step(tmp_path):
    board = HeartbeatBoard(str(tmp_path))
    det, clock = make_detector(board)
    board.publish(1, {"rank": 1, "beat": 1, "status": "serving"})
    det.poll()
    clock.t += 10.0
    guard = StepGuard(det, publish=lambda: None)
    ran = []
    with pytest.raises(MembershipChange):
        guard.run_step(lambda: ran.append(1))
    assert not ran                           # never entered the step


# ---------------------------------------------------------------------- #
# (seed, step)-pure takeover
# ---------------------------------------------------------------------- #
def test_replay_reconstructs_the_exact_feed(tmp_path):
    pipe = ToyPipe()
    src = StreamSource.for_host(pipe, host=1, hosts=2, n_requests=5,
                                capacity=2)
    src.pump()                               # 2 of 5 produced
    produced = [src.take(), src.take()]
    snap = source_snapshot(src)
    replayed = replay_requests(pipe, snap)
    # the whole bounded feed — produced AND never-produced tail
    assert [r.uid for r in replayed] == \
        [1_000_000 + k for k in range(5)]
    for orig, rep in zip(produced, replayed):
        assert rep.uid == orig.uid
        np.testing.assert_array_equal(rep.items, orig.items)
    # journaled uids are never replayed
    again = replay_requests(pipe, snap,
                            exclude={1_000_000, 1_000_002})
    assert [r.uid for r in again] == [1_000_001, 1_000_003, 1_000_004]


def test_replay_endless_stream_covers_the_produced_window(tmp_path):
    src = StreamSource(ToyPipe(), n_requests=None, capacity=3)
    src.pump()
    snap = source_snapshot(src)
    replayed = replay_requests(ToyPipe(), snap)
    assert [r.uid for r in replayed] == [0, 1, 2]


# ---------------------------------------------------------------------- #
# re-admission without double-charged backpressure
# ---------------------------------------------------------------------- #
def test_bounded_queue_requeue_bypasses_capacity_once(tmp_path):
    q = BoundedQueue(2)
    assert q.offer("a") and q.offer("b") and not q.offer("c")
    q.requeue("x")                           # always accepted
    assert len(q) == 3 and q.peek() == "x" and q.full
    assert not q.offer("d")                  # producer pays the overage
    assert [q.poll() for _ in range(3)] == ["x", "a", "b"]
    assert q.offer("d")                      # capacity restored


def test_source_requeue_preserves_budget_and_order(tmp_path):
    src = StreamSource(ToyPipe(), n_requests=4, capacity=2)
    assert src.pump() == 2
    r0, r1 = src.take(), src.take()
    src.requeue([r0, r1])
    assert src.peek().uid == r0.uid          # front, original order
    assert src.produced == 2                 # budget not re-charged
    assert src.pump() == 0 and src.stalls >= 1   # over capacity: stall
    got = [src.take().uid for _ in range(2)]
    assert got == [r0.uid, r1.uid]
    assert src.pump() == 2                   # drained: budget resumes
    assert src.produced == 4


def test_router_requeue_bypasses_admission_limit(tmp_path):
    router = toy_router(queue_limit=1)
    rng = np.random.default_rng(0)
    mk = lambda uid, n: ItemRequest(
        uid=uid, items=rng.uniform(-1, 1, (n, D_IN)).astype(np.float32),
        key=APP)
    assert router.submit(mk(0, 3))
    assert not router.submit(mk(1, 2))       # admission full
    router.requeue([mk(2, 2), mk(3, 1)])     # no-drop re-admission
    assert len(router.queue) == 3
    assert not router.submit(mk(4, 2))       # fresh submits still see
    while router.queue or router.active:     # the backpressure
        router.step()
    assert sorted(st.request.uid for st in router.finished) == [0, 2, 3]
    assert router.submit(mk(5, 1))           # drained: admission back


def test_degrade_to_local_requeues_in_flight_lanes(tmp_path):
    """The degraded-mode switch on a router mid-serve: the member is
    re-placed on the local mesh, in-flight lanes are evicted and
    requeued at the FRONT with their progress, the counters carry
    over, and every admitted request still finishes exactly once with
    every item streamed once (y = 2x + 1)."""
    router = toy_router(queue_limit=2, step_when_idle=True)
    rng = np.random.default_rng(3)
    lengths = {0: 1, 1: 6, 2: 5, 3: 4, 4: 2}
    reqs = {uid: ItemRequest(
        uid=uid, items=rng.uniform(-1, 1, (n, D_IN)).astype(np.float32),
        key=APP) for uid, n in lengths.items()}
    assert router.submit(reqs[0]) and router.submit(reqs[1])
    assert not router.submit(reqs[4])        # admission full: rejected
    router.step()                            # 0 done, 1 streaming
    assert router.submit(reqs[2]) and router.submit(reqs[3])
    router.step()
    router.step()                            # 2 joined the free lane
    before = (list(router.finished), router.items_emitted,
              router.rejected, router.steps)
    in_flight = [router.active[s] for s in sorted(router.active)]
    progress = [(st.request.uid, st.pos) for st in in_flight]
    assert sorted(uid for uid, _ in progress) == [1, 2]
    assert all(pos > 0 for _, pos in progress)
    assert [getattr(e, "uid", None) for e in router.queue] == [3]

    degrade_to_local(router)

    assert not router.active and router.slots == 2
    assert list(router.queue)[:2] == in_flight   # front, slot order
    assert all(isinstance(e, ItemRequestState)
               for e in list(router.queue)[:2])
    assert [(st.request.uid, st.pos) for st in in_flight] == progress
    assert (list(router.finished), router.items_emitted,
            router.rejected, router.steps) == before
    assert not router._spmd_lockstep and not router._local_stream
    assert not router.step_when_idle
    assert router.members[APP].n_chips == 1

    router.run_until_drained()
    done = [st.request.uid for st in router.finished]
    assert sorted(done) == [0, 1, 2, 3]          # 4 was rejected
    assert len(set(done)) == len(done)
    assert router.items_emitted == sum(lengths[u] for u in done)
    for st in router.finished:
        np.testing.assert_allclose(
            st.result, np.asarray(st.request.items) * 2.0 + 1.0,
            rtol=1e-6)


# ---------------------------------------------------------------------- #
# two HA servers, one process: deterministic mid-serve death
# ---------------------------------------------------------------------- #
N_REQ = 6
UID1 = 1_000_000


def _make_server(board, rank, *, takeover="replay",
                 pipeline=None):
    cfg = HAConfig(timeout_s=0.05, retries=2, backoff_s=0.01,
                   idle_sleep_s=0.001, takeover=takeover)
    router = toy_router()
    pipe = pipeline or ToyPipe()
    src = StreamSource.for_host(pipe, host=rank, hosts=2,
                                n_requests=N_REQ, capacity=3)
    return HAFleetServer(router, src, board=board, rank=rank,
                         ranks=(0, 1), pipeline=pipe, key=APP,
                         config=cfg)


def _run_death_scenario(tmp_path, *, takeover):
    board = HeartbeatBoard(str(tmp_path))
    victim = _make_server(board, 0)
    survivor = _make_server(board, 1, takeover=takeover)
    # interleave a few ticks so BOTH are mid-serve with lanes busy …
    for _ in range(3):
        assert victim.serve_tick() == "step"
        assert survivor.serve_tick() == "step"
    assert victim.router.active and survivor.router.active
    victim_journal = board.read(0)
    assert victim_journal["status"] == "serving"
    # … then the victim simply stops ticking (its process died); its
    # board row stays frozen at the last heartbeat
    time.sleep(0.12)                         # let the deadline lapse
    done = survivor.serve(max_ticks=5000)
    return victim, survivor, done, board


def test_survivor_absorbs_dead_feed_with_exact_accounting(tmp_path):
    victim, survivor, done, board = _run_death_scenario(
        tmp_path, takeover="replay")
    assert survivor.detector.dead == {0}
    assert survivor.absorbed == [0]
    expected = set(range(N_REQ)) | {UID1 + k for k in range(N_REQ)}
    victim_completed = set(board.read(0)["completed"])
    survivor_completed = {st.request.uid for st in done}
    # exactly once: completed by exactly one rank, nothing lost
    assert victim_completed | survivor_completed == expected
    assert not victim_completed & survivor_completed
    assert not survivor.rejected_uids
    # and every output is exact (replayed frames identical to dead
    # host's frames, streamed once by the survivor)
    for st in done:
        np.testing.assert_allclose(
            st.result, np.asarray(st.request.items) * 2.0 + 1.0,
            rtol=1e-6)
    assert survivor.degraded_items_per_second > 0


def test_reject_takeover_accounts_without_serving(tmp_path):
    victim, survivor, done, board = _run_death_scenario(
        tmp_path, takeover="reject")
    assert survivor.absorbed == [0]
    victim_completed = set(board.read(0)["completed"])
    survivor_completed = {st.request.uid for st in done}
    rejected = set(survivor.rejected_uids)
    # survivor serves only its own feed …
    assert survivor_completed == {UID1 + k for k in range(N_REQ)}
    # … but still accounts for every item of the dead host's: the
    # unjournaled remainder is EXPLICITLY rejected, never silently lost
    assert victim_completed | rejected == set(range(N_REQ))
    assert not victim_completed & rejected
    # the rejection is journaled on the board too
    assert set(board.read(1)["rejected_uids"]) == rejected


def test_stats_global_assembles_the_fleet_from_any_survivor(tmp_path):
    victim, survivor, done, board = _run_death_scenario(
        tmp_path, takeover="replay")
    gs = survivor.stats_global()             # from rank 1, no rank 0
    victim_completed = set(board.read(0)["completed"])
    assert gs.requests == len(done) + len(victim_completed) == 2 * N_REQ
    # items: exactly-once accounting of requests, at-least-once
    # execution (the victim's partially-streamed lanes replay whole)
    per_feed_items = sum(
        np.asarray(r.items).shape[0]
        for r in replay_requests(ToyPipe(), source_snapshot(
            StreamSource.for_host(ToyPipe(), host=0, hosts=2,
                                  n_requests=N_REQ))))
    assert gs.items >= 2 * per_feed_items
    assert gs.lanes == victim.router.slots + survivor.router.slots
    assert gs.rejected == 0


def test_two_healthy_servers_settle_without_takeover(tmp_path):
    """No failure: both drain their own feeds, see each other 'done'
    on the board, and stop — nothing absorbed, nothing rejected."""
    board = HeartbeatBoard(str(tmp_path))
    a = _make_server(board, 0)
    b = _make_server(board, 1)
    decisions = {"a": None, "b": None}
    for _ in range(5000):
        if decisions["a"] != "stop":
            decisions["a"] = a.serve_tick()
        if decisions["b"] != "stop":
            decisions["b"] = b.serve_tick()
        if decisions["a"] == decisions["b"] == "stop":
            break
    assert decisions == {"a": "stop", "b": "stop"}
    a.publish(status="done")
    b.publish(status="done")
    assert not a.absorbed and not b.absorbed
    assert {st.request.uid for st in a.router.finished} == \
        set(range(N_REQ))
    assert {st.request.uid for st in b.router.finished} == \
        {UID1 + k for k in range(N_REQ)}


# ---------------------------------------------------------------------- #
# the chaos-capable supervisor (jax-free subprocess workers)
# ---------------------------------------------------------------------- #
def test_launch_validates_chaos_arguments():
    with pytest.raises(ValueError, match="on_failure"):
        simdev.launch_local_fleet([sys.executable, "-c", "pass"], 1,
                                  on_failure="retry")
    with pytest.raises(ValueError, match="ha_dir"):
        simdev.launch_local_fleet([sys.executable, "-c", "pass"], 1,
                                  kill_at=(0, 3))
    with pytest.raises(ValueError, match="rank"):
        simdev.launch_local_fleet([sys.executable, "-c", "pass"], 1,
                                  kill_at=(5, 3), ha_dir="/tmp")


def test_worker_result_distinguishes_crash_from_kill():
    mk = simdev.WorkerResult
    assert mk(0, 3, "", "boom").crashed
    assert not mk(0, 0, "", "").crashed
    assert not mk(0, -15, "", "", killed=True).crashed
    assert not mk(0, -9, "", "", injected=True).crashed
    tail = mk(0, 1, "", "\n".join(f"line{i}" for i in range(20)))
    assert tail.stderr_tail.splitlines() == \
        [f"line{i}" for i in range(12, 20)]


_CRASH_OR_SERVE = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ["REPRO_DIST_RANK"])
    if rank == 0:
        print("dying", file=sys.stderr)
        sys.exit(3)
    time.sleep(0.8)
    print("served")
""")


def test_on_failure_continue_lets_survivors_finish():
    results = simdev.launch_local_fleet(
        [sys.executable, "-c", _CRASH_OR_SERVE], 2,
        on_failure="continue", timeout=60.0, poll_s=0.05)
    dead, alive = results
    assert dead.crashed and dead.returncode == 3
    assert "dying" in dead.stderr_tail
    assert alive.returncode == 0 and not alive.killed
    assert "served" in alive.stdout


def test_on_failure_kill_stays_the_default():
    results = simdev.launch_local_fleet(
        [sys.executable, "-c", _CRASH_OR_SERVE], 2,
        timeout=60.0, poll_s=0.05)
    dead, alive = results
    assert dead.crashed and dead.returncode == 3
    assert alive.killed and alive.returncode != 0


_BEATING_WORKER = textwrap.dedent("""
    import json, os, time
    rank = int(os.environ["REPRO_DIST_RANK"])
    root = os.environ["REPRO_FLEET_HA_DIR"]
    for step in range(40):
        path = os.path.join(root, f"rank_{rank}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"rank": rank, "beat": step + 1, "step": step,
                       "status": "serving"}, f)
        os.replace(tmp, path)
        time.sleep(0.05)
    print("finished all steps")
""")


def test_kill_at_injects_at_the_published_step(tmp_path):
    results = simdev.launch_local_fleet(
        [sys.executable, "-c", _BEATING_WORKER], 2,
        on_failure="continue", kill_at=(0, 5), ha_dir=str(tmp_path),
        timeout=60.0, poll_s=0.02)
    victim, other = results
    assert victim.injected and not victim.crashed
    assert victim.returncode not in (0, None)
    journal = simdev.read_board(str(tmp_path), 0)
    assert 5 <= journal["step"] < 40         # mid-serve, not at the end
    assert other.returncode == 0 and "finished all steps" in other.stdout


# ---------------------------------------------------------------------- #
# Deployment.resize: live elastic resize, zero compile passes
# ---------------------------------------------------------------------- #
RESIZE_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.chip import compile_count
    from repro.core.crossbar_layer import MLPSpec, mlp_init
    from repro.deploy import AppSpec, deploy

    dims = (16, 12, 4)
    spec = MLPSpec(dims, activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    d = deploy(AppSpec("app", spec, params=params, lanes_per_chip=2),
               n_chips=2)
    c0 = compile_count()
    chip = d.chip("app")
    rng = np.random.default_rng(0)
    for i in range(6):
        assert d.submit("app", rng.uniform(-1, 1, (5 + i, dims[0]))
                        .astype(np.float32))
    for _ in range(2):
        d.step()                        # lanes mid-request
    d.resize(4)                         # grow under live traffic
    lanes_grown = d.router.slots
    for _ in range(2):
        d.step()
    d.resize(1)                         # shrink under live traffic
    done = d.run_until_drained()
    ok = all(np.allclose(st.result,
                         np.asarray(chip.stream(
                             jnp.asarray(st.request.items))),
                         atol=1e-5) for st in done)
    print(json.dumps({
        "ok": bool(ok), "n": len(done),
        "uids": sorted(st.request.uid for st in done),
        "compile_delta": compile_count() - c0,
        "lanes_grown": lanes_grown, "n_chips": d.n_chips,
        "lanes": d.router.slots,
    }))
""")


def test_deployment_resize_is_zero_compile_and_exact(sim_subprocess):
    out = sim_subprocess(RESIZE_SCRIPT, n_devices=4)
    assert out["ok"], out
    assert out["n"] == 6 and out["uids"] == list(range(6))
    assert out["compile_delta"] == 0         # the tentpole pin
    assert out["lanes_grown"] == 8           # 2 lanes × 4 chips
    assert out["n_chips"] == 1 and out["lanes"] == 2


# ---------------------------------------------------------------------- #
# chaos: real kills, real processes
# ---------------------------------------------------------------------- #
@pytest.mark.chaos
def test_chaos_selftest_cli():
    """The headline artifact end-to-end: kill rank 0 of a federated
    2-host fleet mid-serve; survivors degrade, absorb, account
    exactly; rank 1 reports stats_global; resize is zero-compile."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-m", "repro.fleet", "--chaos-selftest"],
        capture_output=True, text=True, timeout=570,
        env={**os.environ, "PYTHONPATH": simdev.SRC_DIR},
        cwd=simdev.REPO_ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    summary = simdev.last_json_line(out.stdout)
    assert summary["pass"] and summary["kill_rank"] == 0


_LOCKSTEP_HA_WORKER = textwrap.dedent("""
    import json, os, sys
    rank = int(os.environ["REPRO_DIST_RANK"])
    nprocs = int(os.environ["REPRO_DIST_NPROCS"])
    port = int(os.environ["REPRO_DIST_PORT"])
    ha_dir = os.environ["REPRO_FLEET_HA_DIR"]

    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs, process_id=rank)
    import numpy as np
    from repro.core.crossbar_layer import MLPSpec, mlp_init
    from repro.data.pipeline import SensorPipeline
    from repro.deploy import AppSpec, deploy
    from repro.fleet import StreamSource
    from repro.fleet.ha import HAConfig, HAFleetServer, HeartbeatBoard
    from repro.launch.mesh import make_distributed_fleet_mesh

    dims = (784, 64, 10)
    spec = MLPSpec(dims, activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    d = deploy(AppSpec("app", spec, params=params, system="memristor",
                       lanes_per_chip=2, queue_limit=4),
               mesh=make_distributed_fleet_mesh())
    router = d.router
    assert type(router).__name__ == "DistributedMultiAppRouter"
    pipe = SensorPipeline(window=28, stride=18, frames_per_step=1)
    src = StreamSource.for_host(pipe, n_requests=6, capacity=3)
    server = HAFleetServer(
        router, src, board=HeartbeatBoard(ha_dir), rank=rank,
        ranks=range(nprocs), pipeline=pipe, key="app",
        config=HAConfig(timeout_s=1.0, retries=3, backoff_s=0.1,
                        step_sleep_s=0.05))
    done = server.serve()
    out = {"rank": rank, "absorbed": server.absorbed,
           "degraded": not router._spmd_lockstep,
           "completed": sorted(st.request.uid for st in done),
           "ok": src.exhausted}
    print(json.dumps(out), flush=True)
    # after a peer death the jax.distributed shutdown path SIGABRTs;
    # the journal (board) is already the durable record
    sys.stdout.flush()
    os._exit(0)
""")


@pytest.mark.chaos
def test_lockstep_router_degrades_in_place_on_peer_death(tmp_path):
    """The SPMD path: kill the NON-coordinator rank of a real
    jax.distributed fleet mid-collective. The coordinator's guarded
    step must turn the gloo failure into MembershipChange; the server
    degrades the lockstep router onto the local mesh in place and
    finishes BOTH feeds. (Killing the coordinator is unsurvivable at
    the runtime level — that scenario is the federated selftest's.)"""
    results = simdev.launch_local_fleet(
        [sys.executable, "-c", _LOCKSTEP_HA_WORKER], 2,
        devices_per_process=2, on_failure="continue",
        kill_at=(1, 3), ha_dir=str(tmp_path), timeout=570.0,
        poll_s=0.05)
    survivor, victim = results
    assert victim.injected and not victim.crashed
    assert survivor.returncode == 0, survivor.stderr_tail
    out = simdev.last_json_line(survivor.stdout)
    assert out["ok"] and out["absorbed"] == [1] and out["degraded"]
    expected = set(range(6)) | {1_000_000 + k for k in range(6)}
    victim_completed = set(
        (simdev.read_board(str(tmp_path), 1) or {}).get("completed",
                                                        ()))
    assert set(out["completed"]) | victim_completed == expected
    assert not set(out["completed"]) & victim_completed
