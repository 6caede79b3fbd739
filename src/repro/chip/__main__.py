"""CI smoke entry point:  PYTHONPATH=src python -m repro.chip --selftest

Compiles the paper's deep-app MLP (784→200→100→10) onto 1T1M cores,
checks that the mapped stream matches the programmed dense oracle, that
the report reproduces the published core count, and that a one-chip
``deploy()`` of the same network drains a small request burst through
the router correctly. Exit code 0 iff all
checks pass.
"""
from __future__ import annotations

import argparse
import sys


def selftest(verbose: bool = True) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.chip import compile_chip
    from repro.core.crossbar_layer import (MLPSpec, mlp_init, program_mlp,
                                           programmed_mlp_apply)
    from repro.deploy import AppSpec, deploy

    ok = True

    def check(name, cond, detail=""):
        nonlocal ok
        ok = ok and bool(cond)
        if verbose:
            print(f"  [{'ok' if cond else 'FAIL'}] {name}"
                  f"{'  (' + detail + ')' if detail else ''}")

    dims = (784, 200, 100, 10)
    spec = MLPSpec(dims, activation="threshold", out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)
    chip = compile_chip(spec, params=params, system="memristor",
                        items_per_second=1000.0)

    x = jax.random.uniform(jax.random.PRNGKey(1), (128, 784),
                           minval=0, maxval=1)
    y = chip.stream(x)
    oracle = programmed_mlp_apply(program_mlp(params, spec,
                                              mode="crossbar"), x)
    rel = float(jnp.max(jnp.abs(y - oracle)) /
                jnp.maximum(jnp.max(jnp.abs(oracle)), 1e-12))
    check("stream matches programmed dense oracle", rel <= 1e-5,
          f"max rel {rel:.2e}")
    check("output shape", y.shape == (128, 10))

    rep = chip.report()
    # chip.report must agree with the independent costmodel assembly
    # that the Tables II–VI benchmark validates against the paper
    from repro.configs.paper_apps import APPS
    from repro.core.costmodel import specialized_cost
    ref = specialized_cost(APPS["deep"], "memristor")
    check("report reproduces the Tables II-VI deep-app accounting",
          rep.cores_per_replica == ref.mapping.cores_per_replica,
          f"{rep.cores_per_replica} cores/replica")
    check("report power decomposes", abs(
        rep.power_mw - (rep.leak_mw + rep.compute_mw + rep.routing_mw +
                        rep.tsv_mw)) < 1e-9)

    # TDM schedule feasibility: no slot overlap on any link
    overlaps = 0
    for entries in chip.route.schedule.values():
        spans = sorted((s, s + n) for _, s, n in entries)
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            overlaps += a1 > b0
    check("TDM schedule is conflict-free", overlaps == 0)

    d = deploy(AppSpec("deep", spec, params=params, system="memristor",
                       items_per_second=1000.0, lanes_per_chip=3),
               n_chips=1)
    rng = np.random.default_rng(2)
    for i in range(5):
        d.submit("deep", rng.uniform(0, 1, (2 + i, 784)))
    done = d.run_until_drained()
    check("router drains all requests", len(done) == 5)
    served_ok = all(
        np.allclose(st.result,
                    np.asarray(chip.stream(jnp.asarray(st.request.items))),
                    atol=1e-5)
        for st in done)
    check("served outputs match direct stream", served_ok)
    d.close()

    if verbose:
        print(f"selftest: {'PASS' if ok else 'FAIL'}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.chip")
    ap.add_argument("--selftest", action="store_true",
                    help="run the compile→program→stream smoke check")
    args = ap.parse_args(argv)
    if not args.selftest:
        ap.print_help()
        return 2
    return 0 if selftest() else 1


if __name__ == "__main__":
    sys.exit(main())
