"""The LM cells' control readings: the plain reference one step lower in
precision, in the program's place, read by the cell's own check.

    python3 bench/lm_control.py --workload <cell> --seeds <a,b,...> \
        [--mode high] [--steps 256]

For each seed: the cell's weights and requests (``tenants/lm.py``); for
each of the mix's checked lanes, a request's prompt followed by
``--steps`` seeded tokens in place of the tokens the program would
emit; the logits at the mix's checked steps from the reference at
HIGHEST and in ``--mode`` (``high``: the backend's HIGH, three
bfloat16 passes on a TPU; ``bf16``: bfloat16 operands), compared as the
check compares them (``rel_err``, ``rms_err``, near routing ties left
out). One JSON line per seed. Like ``bench/run.py`` it refuses to run
without a TPU, unless ``--cpu`` is given for a rehearsal at the
tenant's CPU size.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import catalog, lm_reference, reference, traffic  # noqa: E402


def readings(cfg: dict, mix: dict, tenant, seed: int, mode: str,
             steps: int) -> dict:
    import numpy as np

    params = tenant.make_params(cfg, seed)
    load = tenant.make_load(cfg, mix, seed, 0.0)
    gen = traffic.rng(seed, 5)
    ys, refs, nears = [], [], []
    for lane in load.check_lanes:
        prompt = load.prompts[lane]
        seq = np.concatenate([prompt, gen.integers(
            0, cfg["vocab_size"], min(steps, load.max_new[lane]))])
        ref, near = lm_reference.forward(cfg, params, seq, len(prompt))
        ctl, _ = lm_reference.forward(cfg, params, seq, len(prompt),
                                      mode=mode)
        at = [len(prompt) + s for s in sorted(load.check_steps)
              if len(prompt) + s < len(seq)]
        ys.append(ctl[at])
        refs.append(ref[at])
        nears.append(near[at])
    near = np.concatenate(nears)
    out = reference.errors(np.concatenate(ys), np.concatenate(refs), near)
    out.update(seed=seed, mode=mode, rows=int(len(near)),
               near=int(near.sum()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", default="high", choices=("high", "bf16"))
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = catalog.cell(catalog.benchmark(), args.workload)
    cfg = catalog.config(cell["config"])
    mix = catalog.mix(cell["traffic"], cell["name"])
    tenant = catalog.tenant(cfg.get("tenant"))
    import jax

    if args.cpu:
        cfg, mix = tenant.rehearsal(cfg, mix)
    elif jax.devices()[0].platform != "tpu":
        print("lm_control: no TPU; nothing ran", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cfg, mix, tenant, seed, args.mode,
                                  args.steps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
