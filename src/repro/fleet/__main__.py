"""CI smoke entry points for the multi-process fabric.

``PYTHONPATH=src python -m repro.fleet --distributed-selftest`` — the
multi-PROCESS fabric: self-spawns N localhost worker processes
(default 2, ``--processes``), each a real ``jax.distributed`` rank
with its own simulated CPU devices (``--chips-per-process``) and gloo
cross-process collectives. Every worker checks, against a locally
recomputed single-chip reference (everything is a pure function of
(seed, step), so no reference data crosses hosts): the distributed
``stream_local`` equals the single-chip stream at rel 0.0 on its row
block; the lockstep :class:`repro.deploy.DistributedMultiAppRouter`
that ``deploy()`` builds on the distributed mesh drains per-host
sensor feeders and its outputs match the direct stream; and the
``stats_global`` roll-up accounts for every host's requests, items and
lanes. The parent supervises the workers (any death kills the rest)
and exits 0 iff every rank passed.

``PYTHONPATH=src python -m repro.fleet --chaos-selftest`` — fault
tolerance: spawns a FEDERATED fleet (independent jax processes over a
shared heartbeat board — see :mod:`repro.fleet.ha` for why not
``jax.distributed``), SIGKILLs one worker mid-serve at a chosen engine
step, and asserts the survivors detect the death, absorb the dead
host's feed, finish degraded, and account for every admitted item of
every host exactly once — audited by the parent from the final board
journals.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def distributed_worker(verbose: bool = True) -> int:
    """One rank of the localhost distributed selftest (spawned by
    :func:`run_distributed_selftest` with the rendezvous in
    ``REPRO_DIST_*`` env vars). Prints one JSON result line; exit code
    0 iff EVERY rank's checks passed (the verdict is allgathered, so
    all ranks agree)."""
    rank = int(os.environ["REPRO_DIST_RANK"])
    nprocs = int(os.environ["REPRO_DIST_NPROCS"])
    port = int(os.environ["REPRO_DIST_PORT"])
    # test hook for the worker-death suite: die before touching jax,
    # leaving the peers blocked in distributed initialize — exactly the
    # hang the launcher's supervision must clean up
    if os.environ.get("REPRO_FLEET_CRASH_RANK") == str(rank):
        print(json.dumps({"rank": rank, "ok": False,
                          "crashed": "injected"}), flush=True)
        return 3

    import jax

    # the CPU client refuses multi-process computations unless it is
    # built with a collectives implementation: set before initialize
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nprocs, process_id=rank)

    import numpy as np
    import jax.numpy as jnp

    from repro.core.crossbar_layer import MLPSpec, mlp_init
    from repro.data.pipeline import SensorPipeline
    from repro.deploy import AppSpec, deploy
    from repro.fleet import StreamSource
    from repro.launch.mesh import make_distributed_fleet_mesh

    ok = True
    out = {"rank": rank, "processes": jax.process_count()}

    def check(name, cond):
        nonlocal ok
        ok = ok and bool(cond)
        if verbose:
            print(f"  [rank {rank}] [{'ok' if cond else 'FAIL'}] "
                  f"{name}", flush=True)

    check("distributed runtime spans the processes",
          jax.process_count() == nprocs and
          jax.process_index() == rank)

    mesh = make_distributed_fleet_mesh()
    n_local = jax.local_device_count()
    check("fleet mesh covers every process's chips",
          mesh.devices.size == nprocs * n_local)

    # the compile is a pure function of the seed, so every rank
    # programs an identical chip — fleet programming moves no bytes
    dims = (784, 200, 100, 10)
    spec = MLPSpec(dims, activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    d = deploy(AppSpec("app", spec, params=params, system="memristor",
                       lanes_per_chip=2, queue_limit=4), mesh=mesh)
    chip = d.chip("app")
    router = d.router
    fleet = router.members["app"]
    check("fleet is distributed",
          fleet.is_distributed and fleet.n_chips == mesh.devices.size
          and fleet.n_local_chips == n_local)

    # distributed stream == single chip, rel 0.0: the global batch is
    # a pure function of its seed, so this rank recomputes it, streams
    # its own row block through the fabric, and checks against a
    # locally evaluated single-chip reference — no data crosses hosts
    rows_per_chip = 3
    B = rows_per_chip * fleet.n_chips
    x_global = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (B, dims[0]), minval=0, maxval=1))
    per_proc = rows_per_chip * n_local
    lo = rank * per_proc
    x_local = x_global[lo:lo + per_proc]
    y_local = fleet.stream_local(x_local)
    with jax.default_device(jax.local_devices()[0]):
        ref = np.asarray(chip.stream(jnp.asarray(x_global)))
    rel = float(np.max(np.abs(y_local - ref[lo:lo + per_proc])) /
                max(np.max(np.abs(ref)), 1e-12))
    out["rel"] = rel
    check("distributed stream == single-chip stream (rel 0.0)",
          rel == 0.0)

    # lockstep router over per-host sensor feeders: rank h streams
    # frames h, h+H, h+2H, … of ONE logical sensor stream
    n_req = 6
    pipe = SensorPipeline(window=28, stride=18, frames_per_step=1)
    src = StreamSource.for_host(pipe, n_requests=n_req, capacity=3)
    done = d.serve(src)
    out["drained"] = len(done)
    check("per-host feeder drains through the lockstep router",
          len(done) == n_req and src.exhausted)
    with jax.default_device(jax.local_devices()[0]):
        served_ok = all(
            np.allclose(st.result,
                        np.asarray(chip.stream(
                            jnp.asarray(st.request.items))),
                        atol=1e-5) for st in done)
    check("routed outputs match the direct stream", served_ok)
    check("latency accounting is monotonic",
          all(st.request.t_submit <= st.t_admit <= st.t_first
              <= st.t_done for st in done))

    local_stats = router.stats().fleet
    global_all = router.stats_global()
    global_stats = global_all.fleet
    out["stats_local"] = dataclasses.asdict(local_stats)
    out["stats_global"] = dataclasses.asdict(global_stats)
    items_per_host = n_req * pipe.items_per_step
    check("stats_global rolls up every host",
          global_stats.requests == n_req * nprocs and
          global_stats.items == items_per_host * nprocs and
          global_stats.lanes == 2 * fleet.n_chips and
          global_stats.steps >= local_stats.steps)

    # the member's report folds the CROSS-HOST served row into the
    # fleet-wide hardware envelope
    rep = fleet.report(global_all.apps["app"])
    check("fleet report serves the global roll-up",
          rep.n_chips == fleet.n_chips and
          rep.served is not None and
          rep.served.items == items_per_host * nprocs and
          rep.served_fraction_of_capacity is not None)

    # every rank reports the fleet-wide verdict (and the same one)
    from jax.experimental import multihost_utils
    verdicts = np.asarray(multihost_utils.process_allgather(
        np.asarray([1 if ok else 0], np.int32)))
    all_ok = bool(verdicts.sum() == nprocs)
    out["ok"] = all_ok
    if verbose:
        print(f"  [rank {rank}] worker: "
              f"{'PASS' if all_ok else 'FAIL'}", flush=True)
    print(json.dumps(out), flush=True)   # JSON verdict last, by contract
    jax.distributed.shutdown()
    return 0 if all_ok else 1


def chaos_worker(verbose: bool = True) -> int:
    """One host of the FEDERATED chaos fleet (spawned by
    :func:`run_chaos_selftest`).

    No ``jax.distributed``: measurement showed the coordination
    service ABORTS every surviving rank within seconds of the
    coordinator dying, so a fleet that must tolerate ANY single host
    loss runs each host as an independent jax process over its own
    local ``"chip"`` mesh, with membership and accounting on the
    shared-filesystem heartbeat board (``REPRO_FLEET_HA_DIR``). This
    worker deploys a 2-chip fabric (of 4 visible simulated devices),
    serves its share of one logical sensor stream through
    :class:`repro.fleet.ha.HAFleetServer`, survives the supervisor
    SIGKILLing a peer mid-serve (detect → absorb the dead host's feed
    → finish degraded), reports the board ``stats_global`` roll-up
    from THIS rank (no host-0 pinning), then resizes the deployment
    back to all 4 chips under zero compile passes."""
    rank = int(os.environ["REPRO_DIST_RANK"])
    nprocs = int(os.environ["REPRO_DIST_NPROCS"])
    ha_dir = os.environ["REPRO_FLEET_HA_DIR"]
    n_req = int(os.environ.get("REPRO_CHAOS_NREQ", "8"))

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.chip import compile_count
    from repro.core.crossbar_layer import MLPSpec, mlp_init
    from repro.data.pipeline import SensorPipeline
    from repro.deploy import AppSpec, deploy
    from repro.fleet import StreamSource
    from repro.fleet.ha import HAConfig, HAFleetServer, HeartbeatBoard

    ok = True
    out = {"rank": rank}

    def check(name, cond):
        nonlocal ok
        ok = ok and bool(cond)
        if verbose:
            print(f"  [rank {rank}] [{'ok' if cond else 'FAIL'}] "
                  f"{name}", flush=True)

    # the compile is (seed, spec)-pure: every host programs an
    # identical 2-chip fabric with no cross-host traffic
    dims = (784, 200, 100, 10)
    spec = MLPSpec(dims, activation="threshold", out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    d = deploy(AppSpec("app", spec, params=params, lanes_per_chip=2,
                       queue_limit=4), n_chips=2)
    c0 = compile_count()

    pipe = SensorPipeline(window=28, stride=18, frames_per_step=1)
    src = StreamSource.for_host(pipe, host=rank, hosts=nprocs,
                                n_requests=n_req, capacity=3)
    # step_sleep_s paces serving at a sensor frame cadence — which is
    # also what makes "mid-serve" a real window for the supervisor's
    # kill injection (raw engine steps are sub-millisecond)
    server = HAFleetServer(
        d.router, src, board=HeartbeatBoard(ha_dir), rank=rank,
        ranks=range(nprocs), pipeline=pipe, key="app",
        config=HAConfig(timeout_s=1.0, retries=3, backoff_s=0.1,
                        step_sleep_s=float(os.environ.get(
                            "REPRO_CHAOS_STEP_SLEEP", "0.05"))))
    done = server.serve()

    out["completed"] = sorted(st.request.uid for st in done)
    out["rejected"] = sorted(server.rejected_uids)
    out["absorbed"] = server.absorbed
    out["degraded_ips"] = server.degraded_items_per_second
    check("own feed drained", src.exhausted)

    # degraded-mode correctness: every routed output (own + absorbed)
    # matches the single-chip direct stream
    chip = d.chip("app")
    with jax.default_device(jax.local_devices()[0]):
        served_ok = all(
            np.allclose(st.result,
                        np.asarray(chip.stream(
                            jnp.asarray(st.request.items))),
                        atol=1e-5) for st in done)
    check("survivor outputs match the direct stream", served_ok)

    if server.absorbed:
        # the failover roll-up, assumable by ANY surviving rank: this
        # rank assembles the fleet view from the board (the dead
        # rank's row is its last journal — exactly the work it
        # provably delivered). Requests are exactly-once; items are
        # at-least-once in the crash window (partially-streamed lanes
        # replay whole), hence == on requests, >= on items.
        gs = server.stats_global()
        out["stats_requests"] = gs.requests
        out["stats_items"] = gs.items
        check("board stats_global accounts every request",
              gs.requests == nprocs * n_req)
        check("board stats_global items cover the stream",
              gs.items >= nprocs * n_req * pipe.items_per_step)
        check("degraded throughput > 0",
              server.degraded_items_per_second > 0)

    # elastic resize back to full size: re-place the programmed plan
    # on all 4 local chips — ZERO compile passes, rel 0.0
    d.resize(4)
    out["resized_chips"] = d.n_chips
    out["compile_delta"] = compile_count() - c0
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(2),
                                      (8, dims[0])), np.float32)
    with jax.default_device(jax.local_devices()[0]):
        ref = np.asarray(chip.stream(jnp.asarray(x)))
    y = np.asarray(d.stream("app", x))
    rel = float(np.max(np.abs(y - ref)) / max(np.max(np.abs(ref)),
                                              1e-12))
    out["resize_rel"] = rel
    check("resize back to full size: zero compile passes, rel 0.0",
          d.n_chips == 4 and compile_count() == c0 and rel == 0.0)

    out["ok"] = ok
    if verbose:
        print(f"  [rank {rank}] chaos worker: "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
    print(json.dumps(out), flush=True)   # JSON verdict last, by contract
    return 0 if ok else 1


def run_chaos_selftest(processes: int = 2, kill_rank: int = 0,
                       kill_step: int = 3, n_requests: int = 8,
                       verbose: bool = True,
                       timeout: float = 600.0) -> bool:
    """Kill a worker mid-serve; assert the fleet degrades instead of
    dying, and that the accounting is EXACT.

    Spawns a federated ``--chaos-worker`` fleet (4 simulated chips
    visible per host, 2 deployed), lets every host start serving, then
    SIGKILLs ``kill_rank`` the moment its published engine step
    reaches ``kill_step`` (``launch_local_fleet(kill_at=…)`` — a real
    external crash, not a cooperative exit). The survivors must finish
    degraded; afterwards the parent audits the union of the final
    heartbeat-board journals for the no-drop/no-dup contract: every
    admitted item of every host's feed — including the dead host's —
    is accounted exactly once (completed by exactly one rank, or
    explicitly rejected). Killing rank 0 by default also pins that the
    ``stats_global`` roll-up needs no host 0."""
    import shutil
    import tempfile

    from repro.launch.simdev import (last_json_line, launch_local_fleet,
                                     read_board)

    ok = True

    def check(name, cond, detail=""):
        nonlocal ok
        ok = ok and bool(cond)
        if verbose:
            print(f"  [{'ok' if cond else 'FAIL'}] {name}"
                  f"{'  (' + str(detail) + ')' if detail else ''}",
                  flush=True)

    ha_dir = tempfile.mkdtemp(prefix="repro_chaos_")
    try:
        argv = [sys.executable, "-m", "repro.fleet", "--chaos-worker"]
        results = launch_local_fleet(
            argv, processes, devices_per_process=4, timeout=timeout,
            on_failure="continue", kill_at=(kill_rank, kill_step),
            ha_dir=ha_dir, poll_s=0.05,
            extra_env={"REPRO_CHAOS_NREQ": str(n_requests)})

        victim = results[kill_rank]
        check("victim was chaos-killed mid-serve (not a clean exit)",
              victim.injected and not victim.crashed and
              victim.returncode not in (0, None),
              f"rank {kill_rank} exit {victim.returncode}")
        victim_journal = read_board(ha_dir, kill_rank) or {}
        check("victim died with work still in flight",
              len(victim_journal.get("completed", ())) < n_requests,
              f"{len(victim_journal.get('completed', ()))} of "
              f"{n_requests} done at death")
        workers = {}
        for r in results:
            if r.rank == kill_rank:
                continue
            if verbose:
                for line in r.stdout.strip().splitlines():
                    print(f"    {line}")
            check(f"survivor {r.rank} finished degraded (exit 0)",
                  r.returncode == 0 and not r.crashed, r.stderr_tail)
            try:
                workers[r.rank] = last_json_line(r.stdout)
            except (ValueError, json.JSONDecodeError):
                workers[r.rank] = {"rank": r.rank, "ok": False,
                                   "error": r.stderr_tail or "no output"}
        ok = ok and all(bool(w.get("ok")) for w in workers.values())

        # EXACT accounting, audited from outside the fleet: the union
        # of the final board journals must cover every uid of every
        # host's bounded feed exactly once
        completed, rejected, expected = [], set(), set()
        for rank in range(processes):
            payload = read_board(ha_dir, rank) or {}
            completed.extend(payload.get("completed", ()))
            rejected |= set(payload.get("rejected_uids", ()))
            snap = payload.get("source")
            if snap is not None:
                expected |= {snap["uid_base"] + k
                             for k in range(int(snap["n_requests"]))}
        comp_set = set(completed)
        check("every host's feed is on the board",
              len(expected) == processes * n_requests,
              f"{len(expected)} uids")
        check("no item completed twice (no dup)",
              len(completed) == len(comp_set))
        check("no item both completed and rejected",
              not (comp_set & rejected))
        check("every admitted item accounted exactly once (no drop)",
              comp_set | rejected == expected,
              f"missing {sorted(expected - comp_set - rejected)[:8]}")

        absorbers = [w for w in workers.values()
                     if kill_rank in w.get("absorbed", ())]
        check("exactly one survivor absorbed the dead rank's feed",
              len(absorbers) == 1)
        if absorbers:
            a = absorbers[0]
            check("a non-zero surviving rank reported stats_global",
                  a.get("rank") != kill_rank and
                  a.get("stats_requests") == processes * n_requests,
                  f"rank {a.get('rank')}: "
                  f"{a.get('stats_requests')} requests")

        summary = {"pass": bool(ok), "processes": processes,
                   "kill_rank": kill_rank, "kill_step": kill_step,
                   "n_requests": n_requests, "workers": workers}
        print(json.dumps(summary), flush=True)
        if verbose:
            print(f"chaos selftest: {'PASS' if ok else 'FAIL'}")
        return ok
    finally:
        shutil.rmtree(ha_dir, ignore_errors=True)


def run_distributed_selftest(processes: int = 2,
                             chips_per_process: int = 2,
                             verbose: bool = True,
                             timeout: float = 600.0) -> bool:
    """Parent of the distributed selftest: spawn one
    ``--distributed-worker`` per rank on localhost (supervised — a dead
    worker takes the fleet down instead of hanging it), then aggregate
    the per-rank JSON verdicts. Prints a final JSON summary line."""
    from repro.launch.simdev import last_json_line, launch_local_fleet

    argv = [sys.executable, "-m", "repro.fleet", "--distributed-worker"]
    results = launch_local_fleet(argv, processes,
                                 devices_per_process=chips_per_process,
                                 timeout=timeout)
    workers = []
    ok = True
    for r in results:
        if verbose:
            for line in r.stdout.strip().splitlines():
                print(f"    {line}")
        try:
            workers.append(last_json_line(r.stdout))
        except (ValueError, json.JSONDecodeError):
            workers.append({"rank": r.rank, "ok": False,
                            "error": (r.stderr[-800:] or "no output")})
        ok = ok and r.returncode == 0 and \
            bool(workers[-1].get("ok", False))
        if r.returncode != 0 and verbose:
            print(f"  worker {r.rank}: exit {r.returncode}"
                  f"{' (terminated by supervisor)' if r.killed else ''}")
            if r.stderr.strip():
                print("    " + "\n    ".join(
                    r.stderr.strip().splitlines()[-8:]))
    summary = {"pass": bool(ok), "processes": processes,
               "chips_per_process": chips_per_process,
               "workers": workers}
    print(json.dumps(summary), flush=True)
    if verbose:
        print(f"distributed selftest: {'PASS' if ok else 'FAIL'}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.fleet")
    ap.add_argument("--distributed-selftest", action="store_true",
                    help="self-spawn a localhost jax.distributed fleet "
                         "and check the multi-process fabric")
    ap.add_argument("--processes", type=int, default=2,
                    help="worker processes for --distributed-selftest")
    ap.add_argument("--chips-per-process", type=int, default=2,
                    help="simulated chips (devices) per worker process")
    ap.add_argument("--distributed-worker", action="store_true",
                    help=argparse.SUPPRESS)   # spawned, not typed
    ap.add_argument("--chaos-selftest", action="store_true",
                    help="kill a worker mid-serve and check the fleet "
                         "degrades with exact item accounting")
    ap.add_argument("--kill-rank", type=int, default=0,
                    help="which rank the chaos selftest kills "
                         "(default 0: also pins host-0-free stats)")
    ap.add_argument("--kill-step", type=int, default=3,
                    help="engine step at which the victim is killed")
    ap.add_argument("--chaos-worker", action="store_true",
                    help=argparse.SUPPRESS)   # spawned, not typed
    args = ap.parse_args(argv)
    if args.distributed_worker or args.chaos_worker:
        if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count="
                + os.environ.get("REPRO_DIST_DEVICES", "1"))
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return chaos_worker() if args.chaos_worker \
            else distributed_worker()
    if args.distributed_selftest:
        return 0 if run_distributed_selftest(
            args.processes, args.chips_per_process) else 1
    if args.chaos_selftest:
        return 0 if run_chaos_selftest(
            args.processes, kill_rank=args.kill_rank,
            kill_step=args.kill_step) else 1
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
