"""Readings that a cell's limit is set from, taken on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out chiprun_out/control.json]

For every seed, in one process: deploy the cell's configuration with
that seed's weights, drive its traffic for ``s`` seconds exactly as a
benchmark run does (the sensor tenant's weights, deployment, traffic
and window), and compare the answers with the reference (the program's
reading). On the same answers, put the control in the
program's place: the reference one step lower in precision
(``check.control`` of the configuration) and read the same number (the
control's reading). The limit lies above the largest program reading
and below the smallest control reading. The benchmark's own runs never
run this. It knows only the sensor tenant (``tenants/sensor.py``); a
tenant of another kind brings its own readings.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import catalog, run  # noqa: E402


def readings(cell_name: str, seed: int, seconds: float) -> dict:
    import jax

    from bench import reference

    bench = catalog.benchmark()
    cell = catalog.cell(bench, cell_name)
    cfg = catalog.config(cell["config"])
    mix = catalog.mix(cell["traffic"], cell["name"])
    sensor = catalog.tenant("sensor")
    params = jax.block_until_ready(sensor.make_params(cfg, seed))
    dep = sensor.deploy(cfg, params, cell["chips"])
    load = sensor.make_load(cfg, mix, seed, seconds)
    sensor.warm(dep, cfg, load)
    win = sensor.window(dep, cfg, mix, load, seconds)
    dep.close()
    del dep
    gc.collect()
    rows, ys = sensor.answers(cfg, win)
    ref, near = reference.forward(cfg, params, load.pool)
    ctl = reference.control_outputs(cfg, params, load.pool)
    out = {"cell": cell_name, "seed": seed, "answers": int(len(rows)),
           "near": int(near[rows].sum()),
           "failed": win.attempted - win.finished,
           "program": reference.errors(ys, ref[rows], near[rows]),
           "control": reference.errors(ctl[rows], ref[rows], near[rows])}
    if cfg["check"]["control"] == "bf16x3":
        high = reference.forward(cfg, params, load.pool, mode="high")[0]
        out["backend_high"] = reference.errors(high[rows], ref[rows],
                                               near[rows])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = catalog.cell(catalog.benchmark(), args.workload)
    run.configure_jax()
    try:
        run.require_devices(cell["chips"])
    except run.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(args.workload, seed, args.seconds)
        print(json.dumps(r), flush=True)
        rows.append(r)
    summary = {"cell": args.workload}
    for name in rows[0]["program"]:
        summary[name] = {
            "program_max": max(r["program"][name] for r in rows),
            "control_min": min(r["control"][name] for r in rows)}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
