"""Serving example: continuous batching over a reduced assigned arch,
plus declarative multi-app deployment of compiled crossbar chips.

Part 1 submits a burst of mixed-length LM requests, reports per-request
latency, engine throughput and slot utilization. The decode step is the
exact function the multi-pod dry-run lowers for the ``decode_*`` shapes.

Part 2 is the paper's own serving story on the same slot-scheduling
contract: one declarative ``deploy()`` spec compiles an MLP classifier
onto simulated 1T1M crossbars, places it over the visible devices as a
fleet member, and wires the continuous-batching router
(``deploy()`` → ``MultiAppRouter`` → member).

Part 3 is what the deployment API adds: a SECOND tenant co-resident on
the same fabric (the paper's multi-application story, Tables II–VI),
with per-app lanes, per-app stats inside one fleet roll-up, and a live
``reprogram`` weight swap that never recompiles.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.deploy import AppSpec, DeploymentSpec, deploy
from repro.models import model as model_lib
from repro.serving.engine import Engine, Request


def main():
    cfg = get_reduced("qwen1.5-0.5b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=4, cache_len=128)

    reqs = [Request(uid=i, prompt=[7 + i, 3, 11, 2][: 2 + i % 3],
                    max_new_tokens=4 + 3 * (i % 4)) for i in range(10)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    steps = 0
    emitted = 0
    while eng.queue or eng.active:
        emitted += eng.step()
        steps += 1
    dt = time.perf_counter() - t0

    print(f"== continuous batching: {len(reqs)} requests, 4 slots ==")
    print(f"{'uid':>4s} {'prompt':>7s} {'new':>4s} {'prefill ms':>11s}")
    for st in sorted(eng.finished, key=lambda s: s.request.uid):
        print(f"{st.request.uid:4d} {len(st.request.prompt):7d} "
              f"{len(st.generated):4d} {st.prefill_s * 1e3:11.1f}")
    total_new = sum(len(st.generated) for st in eng.finished)
    print(f"\n{total_new} tokens in {steps} engine steps, {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s on 1 CPU core; "
          f"slot efficiency {total_new / max(steps * eng.slots, 1):.0%})")

    serve_crossbar_stream()
    serve_two_tenants()


def serve_crossbar_stream(n_requests: int = 12, slots: int = 4):
    """Deploy a classifier app once, then let the continuous-batching
    router serve a burst of item streams against the programmed state
    (§III.D stream-many — deploy() → MultiAppRouter → member)."""
    print("\n== compiled-chip classifier serving (repro.deploy) ==")
    spec = MLPSpec((64, 48, 10), activation="threshold",
                   out_activation="linear")
    params = mlp_init(jax.random.PRNGKey(0), spec)

    t0 = time.perf_counter()
    d = deploy(AppSpec("classify", spec, params=params,
                       system="memristor", lanes_per_chip=slots))
    t_prog = time.perf_counter() - t0

    rng = np.random.default_rng(1)
    bursts = [rng.uniform(-1, 1, (8 + 5 * (i % 4), 64))
              .astype(np.float32) for i in range(n_requests)]
    for items in bursts:
        d.submit("classify", items)
    d.run_until_drained()          # ONE fleet batch per engine step
    stats = d.stats().fleet
    print(f"  deployed once in {t_prog * 1e3:.1f} ms "
          f"({d.chip('classify').total_cores} cores x {d.n_chips} "
          f"chip(s)); {len(bursts)} requests / {stats.items} "
          f"items in {stats.steps} engine steps, "
          f"{stats.wall_s * 1e3:.1f} ms "
          f"({stats.items_per_second:.0f} items/s; slot efficiency "
          f"{stats.occupancy:.0%}; zero re-programming)")
    print(f"  per-request latency: p50 "
          f"{stats.latency_s_p50 * 1e3:.1f} ms, p95 "
          f"{stats.latency_s_p95 * 1e3:.1f} ms "
          f"(mean queue wait {stats.wait_s_mean * 1e3:.1f} ms)")
    d.close()


def serve_two_tenants(n_requests: int = 8):
    """Two apps co-resident on ONE fabric: per-app lane budgets, mixed
    traffic through one router, per-app stats inside one fleet
    roll-up, and a live weight swap for one tenant (reprogram — zero
    recompiles, the other tenant never notices)."""
    print("\n== two tenants, one fabric (repro.deploy) ==")
    spec_cls = MLPSpec((64, 48, 10), activation="threshold",
                       out_activation="linear")
    spec_det = MLPSpec((32, 16, 2), activation="threshold",
                       out_activation="linear")
    p_cls = mlp_init(jax.random.PRNGKey(0), spec_cls)
    p_det = mlp_init(jax.random.PRNGKey(1), spec_det)
    d = deploy(DeploymentSpec(apps=(
        AppSpec("classify", spec_cls, params=p_cls, system="1t1m",
                lanes_per_chip=3),
        AppSpec("detect", spec_det, params=p_det, system="sram",
                lanes_per_chip=1),
    )))
    rng = np.random.default_rng(2)
    for i in range(n_requests):
        d.submit("classify",
                 rng.uniform(-1, 1, (6 + i, 64)).astype(np.float32))
        d.submit("detect",
                 rng.uniform(-1, 1, (4, 32)).astype(np.float32))
    d.run_until_drained()
    # live §III.D weight swap: re-encode ONE tenant's tiles, no compile
    d.reprogram("detect", mlp_init(jax.random.PRNGKey(9), spec_det))
    d.submit("detect", rng.uniform(-1, 1, (4, 32)).astype(np.float32))
    d.run_until_drained()
    stats = d.stats()
    for name, s in stats.apps.items():
        print(f"  {name:>9s}: {s.requests} req / {s.items} items on "
              f"{s.lanes} lanes (p95 {s.latency_s_p95 * 1e3:.1f} ms)")
    print(f"      fleet: {stats.fleet.requests} req / "
          f"{stats.fleet.items} items "
          f"({stats.fleet.items_per_second:.0f} items/s; detect "
          f"reprogrammed live, zero recompiles)")
    print("  " + str(d.report()).replace("\n", "\n  "))
    d.close()


if __name__ == "__main__":
    main()
