import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")
# The two lines above MUST precede any jax-importing module: jax locks the
# device count at first init. DRYRUN_XLA_FLAGS lets tests use fewer
# placeholder devices.

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
  jit(step).lower(ShapeDtypeStructs).compile()
then record memory_analysis(), cost_analysis() and the parsed collective
schedule into one JSON per cell. No real arrays are ever allocated.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out experiments/dryrun
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k \
      --mesh single --reduced          # quick CI-sized check
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import (ARCH_IDS, SHAPES_BY_NAME, applicable, get_config,
                           get_reduced)
from repro.launch import mesh as mesh_lib
from repro.launch import roofline
from repro.launch import specs as specs_lib
from repro.launch.rules import effective_dp, kv_repeat_for, make_rules
from repro.optim.adamw import AdamW, cosine_schedule
from repro.sharding import axis_rules
from repro.train import steps as steps_lib


def _mem_dict(ma) -> dict:
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
        "peak_bytes_per_device": (ma.argument_size_in_bytes +
                                  ma.output_size_in_bytes +
                                  ma.temp_size_in_bytes -
                                  ma.alias_size_in_bytes),
    }


def lower_cell(cfg, shape_cfg, mesh, *, verbose: bool = True,
               counting: bool = False):
    """Build + lower + compile one cell; returns result dict.

    ``counting=True`` lowers the exact-counting variant: layer and
    query-chunk scans fully unrolled and grad-accum microbatching off.
    XLA's ``cost_analysis()`` counts while-loop bodies ONCE (verified —
    see EXPERIMENTS.md §Roofline methodology), so roofline FLOP/byte/
    collective terms come from this program; memory fit and the
    production schedule come from the scanned program, which is what
    actually runs.
    """
    n_dev = mesh.devices.size
    tp = mesh_lib.tp_degree(mesh)
    cfg = cfg.replace(kv_repeat=kv_repeat_for(cfg, tp))
    dp = effective_dp(cfg, mesh)
    if counting:
        # unroll every structural scan (layers, q-chunks, grad-accum) so
        # cost_analysis and the collective parse see every op; keep
        # remat + accum as production so liveness ≈ the real program
        cfg = cfg.replace(scan_layers=False)
    mode = shape_cfg.kind
    rules = make_rules(cfg, mesh, mode, global_batch=shape_cfg.global_batch)
    t0 = time.time()

    with axis_rules(mesh, rules):
        psh = specs_lib.param_shardings(cfg, mesh)
        pshapes = specs_lib.param_shapes(cfg)
        if mode == "train":
            opt = AdamW(lr=cosine_schedule(3e-4, 100, 10_000))
            oshapes = specs_lib.opt_shapes(cfg, opt, pshapes)
            osh = specs_lib.opt_shardings(psh, mesh)
            bshapes, bsh = specs_lib.batch_specs(cfg, shape_cfg, mesh,
                                                 with_labels=True)
            step, accum = steps_lib.make_train_step(
                cfg, opt, global_batch=shape_cfg.global_batch, dp=dp)
            jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, bshapes)
        elif mode == "prefill":
            bshapes, bsh = specs_lib.batch_specs(cfg, shape_cfg, mesh,
                                                 with_labels=False)
            step = steps_lib.make_prefill_step(cfg)
            accum = 1
            jitted = jax.jit(step, in_shardings=(psh, bsh))
            lowered = jitted.lower(pshapes, bshapes)
        else:  # decode
            (cshape, tshape, pshape), (cshard, tshard, pshard) = \
                specs_lib.decode_specs(cfg, shape_cfg, mesh)
            step = steps_lib.make_decode_step(cfg)
            accum = 1
            jitted = jax.jit(step, in_shardings=(psh, cshard, tshard, pshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(pshapes, cshape, tshape, pshape)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = roofline.parse_collectives(hlo, n_dev)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev_hlo = float(cost.get("bytes accessed", 0.0))
    # memory term: analytic HBM traffic (see roofline.analytic_memory_
    # bytes for why neither HLO byte count is usable); the raw HLO value
    # is recorded alongside for reference.
    bytes_dev = roofline.analytic_memory_bytes(
        cfg, shape_cfg, n_devices=n_dev, dp=dp, tp=tp, accum=accum)
    tt = roofline.terms(flops_dev, bytes_dev, coll.wire_bytes)
    mf = roofline.model_flops(cfg, shape_cfg)
    hlo_total = flops_dev * n_dev

    res = {
        "arch": cfg.name, "shape": shape_cfg.name, "mode": mode,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "n_devices": n_dev,
        "grad_accum": accum,
        "kv_repeat": cfg.kv_repeat,
        "counting": counting,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": _mem_dict(ma),
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "hlo_unfused_bytes_per_device": bytes_dev_hlo},
        "collectives": {"wire_bytes_per_device": coll.wire_bytes,
                        "raw_bytes_per_device": coll.raw_bytes,
                        "by_op": coll.by_op, "counts": coll.counts},
        "roofline": tt,
        "model_flops": mf,
        "hlo_flops_total": hlo_total,
        "useful_flops_frac": (mf / hlo_total) if hlo_total else None,
        "status": "ok",
    }
    if verbose:
        peak = res["memory"]["peak_bytes_per_device"] / 2**30
        tag = "count" if counting else "prod"
        print(f"  {cfg.name:>22s} {shape_cfg.name:>12s} {res['mesh']:>9s} "
              f"[{tag}] compile={t_compile:6.1f}s peak={peak:6.2f}GiB "
              f"dom={tt['dominant']:<10s} bound={tt['bound_s']*1e3:8.3f}ms "
              f"useful={res['useful_flops_frac'] and round(res['useful_flops_frac'],3)}")
    return res


def _stack_unit(cfg) -> int:
    """Smallest layer count that tiles the stack's repeating pattern."""
    if cfg.shared_attn_every:
        return cfg.shared_attn_every
    if cfg.slstm_period:
        return cfg.slstm_period
    return 2  # covers gemma2's local/global alternation; 2 == 2x any


def counting_terms(cfg, shape_cfg, mesh, *, verbose: bool = True) -> dict:
    """Exact-counting roofline inputs by finite differencing.

    A full-depth unrolled lowering is exact but slow (≈6 min for a 24L
    model at 256 partitions). Every stack here is a repeating pattern of
    ``unit`` layers, so FLOPs and collective bytes are *affine in depth*:
    lower the unrolled program at k and 2k layers, take the slope as the
    per-unit cost and extrapolate to the real depth. Exact for
    homogeneous stacks up to XLA boundary effects (validated against the
    full 24-layer unrolled qwen train cell — see EXPERIMENTS.md
    §Roofline methodology).
    """
    unit = _stack_unit(cfg)
    k1, k2 = unit, 2 * unit
    if cfg.num_layers <= k2:
        c = lower_cell(cfg, shape_cfg, mesh, verbose=verbose,
                       counting=True)
        return {"method": "full-unroll", "flops_dev":
                c["cost"]["flops_per_device"],
                "wire_bytes_dev": c["collectives"]["wire_bytes_per_device"],
                "by_op": c["collectives"]["by_op"],
                "counts": c["collectives"]["counts"],
                "compile_s": c["compile_s"]}
    r1 = lower_cell(cfg.replace(num_layers=k1), shape_cfg, mesh,
                    verbose=verbose, counting=True)
    r2 = lower_cell(cfg.replace(num_layers=k2), shape_cfg, mesh,
                    verbose=verbose, counting=True)

    def extrap(a, b):
        slope = (b - a) / (k2 - k1)
        return b + slope * (cfg.num_layers - k2)

    f1 = r1["cost"]["flops_per_device"]
    f2 = r2["cost"]["flops_per_device"]
    w1 = r1["collectives"]["wire_bytes_per_device"]
    w2 = r2["collectives"]["wire_bytes_per_device"]
    by_op = {}
    ops = set(r1["collectives"]["by_op"]) | set(r2["collectives"]["by_op"])
    for op in ops:
        by_op[op] = extrap(r1["collectives"]["by_op"].get(op, 0.0),
                           r2["collectives"]["by_op"].get(op, 0.0))
    counts = {}
    for op in ops:
        counts[op] = int(round(extrap(
            r1["collectives"]["counts"].get(op, 0),
            r2["collectives"]["counts"].get(op, 0))))
    return {"method": f"fd-unroll(k={k1},{k2})",
            "flops_dev": extrap(f1, f2),
            "wire_bytes_dev": extrap(w1, w2),
            "by_op": by_op, "counts": counts,
            "compile_s": r1["compile_s"] + r2["compile_s"]}


def lower_cell_full(cfg, shape_cfg, mesh, *, verbose: bool = True,
                    with_counting: bool = True):
    """Production lowering (memory fit + schedule) merged with the
    exact-counting roofline terms."""
    res = lower_cell(cfg, shape_cfg, mesh, verbose=verbose)
    if with_counting:
        n_dev = mesh.devices.size
        tp = mesh_lib.tp_degree(mesh)
        dp = effective_dp(cfg, mesh)
        cnt = counting_terms(cfg, shape_cfg, mesh, verbose=verbose)
        bytes_dev = roofline.analytic_memory_bytes(
            cfg, shape_cfg, n_devices=n_dev, dp=dp, tp=tp,
            accum=res["grad_accum"])
        tt = roofline.terms(cnt["flops_dev"], bytes_dev,
                            cnt["wire_bytes_dev"])
        mf = roofline.model_flops(cfg, shape_cfg)
        hlo_total = cnt["flops_dev"] * n_dev
        res["counting_run"] = cnt
        res["roofline"] = tt
        res["model_flops"] = mf
        res["hlo_flops_total"] = hlo_total
        res["useful_flops_frac"] = (mf / hlo_total) if hlo_total else None
        res["collectives"] = {"wire_bytes_per_device":
                              cnt["wire_bytes_dev"],
                              "by_op": cnt["by_op"],
                              "counts": cnt["counts"],
                              "source": cnt["method"]}
        res["cost"]["flops_per_device"] = cnt["flops_dev"]
        res["cost"]["bytes_per_device"] = bytes_dev
        if verbose:
            print(f"  {cfg.name:>22s} {shape_cfg.name:>12s} ROOFLINE "
                  f"[{cnt['method']}] dom={tt['dominant']:<10s} "
                  f"bound={tt['bound_s'] * 1e3:8.3f}ms "
                  f"useful={round(res['useful_flops_frac'], 3)}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--reduced", action="store_true",
                    help="use reduced configs (CI smoke)")
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have JSON")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES_BY_NAME) if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            cfg = get_reduced(arch) if args.reduced else get_config(arch)
            shape_cfg = SHAPES_BY_NAME[shape_name]
            ok, reason = applicable(cfg, shape_cfg)
            for multi in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                path = outdir / f"{tag}.json"
                if path.exists() and not args.force:
                    continue
                if not ok:
                    path.write_text(json.dumps({
                        "arch": arch, "shape": shape_name,
                        "mesh": "2x16x16" if multi else "16x16",
                        "status": "skip", "reason": reason}, indent=1))
                    print(f"  {arch:>22s} {shape_name:>12s} SKIP: {reason}")
                    continue
                try:
                    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
                    # single-pod cells carry the roofline → add the
                    # exact-counting lowering; multi-pod cells prove
                    # shardability/fit only.
                    res = lower_cell_full(cfg, shape_cfg, mesh,
                                          with_counting=not multi)
                except Exception as e:  # noqa: BLE001 - record, keep going
                    n_fail += 1
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi else "16x16",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"  {arch:>22s} {shape_name:>12s} ERROR: {e!r}")
                path.write_text(json.dumps(res, indent=1))
    print(f"dry-run complete; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
