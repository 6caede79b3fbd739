"""Multi-process fleet fabric: real ``jax.distributed`` ranks on
localhost (gloo CPU collectives), spawned and supervised by
:func:`repro.launch.simdev.launch_local_fleet`.

Everything here is behind the ``distributed`` marker (skipped in the
default tier-1 run — these spawn whole jax processes): enable with
``pytest --run-distributed`` or ``REPRO_RUN_DISTRIBUTED=1``. The
worker body under test is the shipping one
(``python -m repro.fleet --distributed-worker``), so what the suite
pins is exactly what ``--distributed-selftest`` ships:

  * ``ShardedChip.stream_local`` == single-chip stream at rel 0.0 on
    every rank's row block (each rank recomputes its (seed, step)-pure
    reference locally — no reference data crosses hosts);
  * ``DistributedMultiAppRouter.stats_global`` accounts for every host's
    requests/items/lanes, agrees across ranks, and matches the pure
    ``merge_stats`` roll-up of the per-host stats;
  * a dead worker takes the fleet down promptly (supervised shutdown)
    instead of leaving peers blocked in a collective forever.
"""
import subprocess
import sys
import time

import pytest

from repro.launch import simdev
from repro.serving.stats import RouterStats, merge_stats

pytestmark = pytest.mark.distributed

WORKER = [sys.executable, "-m", "repro.fleet", "--distributed-worker"]


def test_two_process_stream_rel0_and_stats_rollup(launch_fleet):
    results = launch_fleet(WORKER, 2, devices_per_process=2,
                           timeout=600)
    assert [r.returncode for r in results] == [0, 0], \
        "\n".join(r.stderr[-1500:] for r in results)
    workers = [simdev.last_json_line(r.stdout) for r in results]

    for w in workers:
        assert w["ok"]
        assert w["rel"] == 0.0        # exact, per rank, on its rows
        assert w["drained"] == 6      # its own feeder fully served

    # the collective roll-up is identical on every rank …
    g = workers[0]["stats_global"]
    assert all(w["stats_global"] == g for w in workers)
    # … and accounts for exactly the hosts' local counters
    for key in ("requests", "items", "rejected", "lanes"):
        assert g[key] == sum(w["stats_local"][key] for w in workers)
    assert g["steps"] == max(w["stats_local"]["steps"]
                             for w in workers)
    # lockstep: every rank ran the same number of engine steps
    assert len({w["stats_local"]["steps"] for w in workers}) == 1

    # the pure merge (no collectives) agrees on everything it can
    # compute exactly from per-host stats
    local_stats = [RouterStats(**w["stats_local"]) for w in workers]
    m = merge_stats(local_stats)
    assert (m.requests, m.items, m.rejected, m.lanes, m.steps) == \
        (g["requests"], g["items"], g["rejected"], g["lanes"],
         g["steps"])
    assert m.latency_s_p95 >= g["latency_s_p95"] - 1e-9  # upper bound


def test_distributed_selftest_cli_passes():
    """The acceptance entry point, end to end: the parent self-spawns
    2 localhost processes and exits 0 with a PASS summary."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.fleet", "--distributed-selftest",
         "--processes", "2", "--chips-per-process", "2"],
        capture_output=True, text=True, timeout=600,
        env=simdev.simulated_device_env(1), cwd=simdev.REPO_ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    summary = simdev.last_json_line(out.stdout)
    assert summary["pass"] and len(summary["workers"]) == 2
    assert all(w["rel"] == 0.0 for w in summary["workers"])


def test_worker_death_takes_the_fleet_down_promptly(launch_fleet):
    """Rank 1 dies before joining the rendezvous (injected via
    REPRO_FLEET_CRASH_RANK); rank 0 is then blocked in
    ``jax.distributed.initialize`` waiting for a peer that will never
    come. The supervisor must notice the death and terminate rank 0
    within seconds — not the coordination service's multi-minute
    timeout — and report who died vs who was cleaned up."""
    t0 = time.monotonic()
    results = launch_fleet(
        WORKER, 2, devices_per_process=1, timeout=120,
        extra_env={"REPRO_FLEET_CRASH_RANK": "1"})
    wall = time.monotonic() - t0
    assert wall < 90, f"shutdown took {wall:.0f}s — supervisor hung"
    dead = results[1]
    survivor = results[0]
    assert dead.returncode == 3 and not dead.killed
    assert simdev.last_json_line(dead.stdout)["crashed"] == "injected"
    # the survivor did not exit on its own — the supervisor took it
    # down (SIGTERM → negative returncode on POSIX)
    assert survivor.killed and survivor.returncode != 0
