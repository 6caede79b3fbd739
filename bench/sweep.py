"""Find a serve cell's knee: its highest sustained offered rate.

    python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 \
        --seconds <s> --seed <n>

Deploys the cell once, then offers its open-loop mix at each rate for
``s`` seconds and prints one JSON line per rate: requests and items
offered per second, the p50 and p95 latency from scheduled arrival,
the p95 of the requests that arrived in the first and in the last
third of the window, and how long the router took after the window
to finish what arrived in it. A rate is sustained while the last
third's p95 stays near the first third's and the drain is short; above
the knee the queue grows all through the window. The benchmark's own
runs never run this; a cell's rate is fixed in ``cells/<cell>.json``.
It knows only the sensor tenant (``tenants/sensor.py``).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import catalog, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = catalog.benchmark()
    cell = catalog.cell(bench, args.workload)
    cfg = catalog.config(cell["config"])
    mix = catalog.mix(cell["traffic"], cell["name"])
    if mix["loop"] != "open":
        print("sweep: only an open-loop mix has a knee", file=sys.stderr)
        return 2
    run.configure_jax()
    try:
        run.require_devices(cell["chips"])
    except run.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    import jax
    import numpy as np

    from bench import loops, traffic

    sensor = catalog.tenant("sensor")
    app, d_in = cfg["app"], cfg["dims"][0]
    params = jax.block_until_ready(sensor.make_params(cfg, args.seed))
    dep = sensor.deploy(cfg, params, cell["chips"])
    warmed = False
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        sched = traffic.open_schedule(m, args.seed, args.seconds, d_in)
        if not warmed:
            loops.warm_open(dep, app, sched, dep.router.slots)
            warmed = True
        t0 = time.perf_counter()
        win = loops.open_loop(dep, app, sched, args.seconds,
                              float(mix["drain_s"]))
        wall = time.perf_counter() - t0
        lat, arr = win.latency_s, win.arrival_s
        third = args.seconds / 3.0
        first, last = lat[arr < third], lat[arr >= 2 * third]
        print(json.dumps({
            "cell": cell["name"], "rate_per_s": rate,
            "items_per_s_offered": float(sched.n.sum() / args.seconds),
            "attempted": win.attempted, "finished": win.finished,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "p95_first_third_ms": float(np.percentile(first, 95) * 1e3),
            "p95_last_third_ms": float(np.percentile(last, 95) * 1e3),
            "drain_s": wall - args.seconds, "steps": win.steps,
            "late_p99_ms": float(np.percentile(win.late_s, 99) * 1e3)}),
            flush=True)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
