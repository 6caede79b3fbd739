"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, no children. It looks the cell up in ``BENCHMARK.json``
and its configuration's tenant up in ``tenants/`` (``catalog.tenant``),
and refuses to run without a TPU (or with fewer chips than the cell
asks for). The tenant makes the parameters on the device from the
seed, deploys them, builds the load from the seed on the host and warms
up the cell's own shapes; then the tenant's window measures for ``s``
seconds. The harness reads the device's peak memory, frees the
deployment, and has the tenant check the window's answers against its
plain reference: each number it returns is held to the configuration's
``check.limits``, and no call or request may fail.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from the program's
``repro.obs`` spans and counters and from a profiler trace of the last
seconds of the window. The last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(traced) ``breakdown``, and last ``checks``, each number compared
beside its limit. The same numbers are the last lines of stderr.

JAX's persistent compile cache lives at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and at ``<checkout>/.jax_cache`` otherwise.
"""
import time

T_PROCESS = time.perf_counter()         # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import catalog  # noqa: E402

SLICE_S = 2.0           # profiled seconds at the end of a traced window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax() -> None:
    """The persistent compile cache, kept for every program so that
    only a checkout's first run of a cell compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_devices(chips: int) -> list:
    """The chips the cell runs on; NoAccelerator without a TPU or with
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts XLA backend compilations (cache hits included)."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def span_stats(events) -> dict:
    out: dict = {}
    for ev in events:
        if ev.get("ph") == "X":
            s = out.setdefault(ev["name"], {"count": 0, "seconds": 0.0})
            s["count"] += 1
            s["seconds"] += ev["dur"] * 1e-6
    return out


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, tenant,
             seed: int, seconds: float, trace: bool, devices: list) -> dict:
    """One run of ``cell``: ``tenant`` (``catalog.tenant``) makes the
    parameters, the deployment, the load, the window and the check."""
    import jax
    import numpy as np

    from bench import loops

    chips = cell["chips"]
    used = devices[:chips]
    kind = used[0].device_kind
    peaks = catalog.peaks(kind) if used[0].platform == "tpu" else None

    params = jax.block_until_ready(tenant.make_params(cfg, seed))
    t = time.perf_counter()
    dep = tenant.deploy(cfg, params, chips)
    deploy_s = time.perf_counter() - t
    log(f"deploy: {deploy_s:.6f} s; report: {dep.report()}")
    load = tenant.make_load(cfg, mix, seed, seconds)
    tenant.warm(dep, cfg, load)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else loops.no_span
    slice_ = loops.Slice(log_dir, max(0.0, seconds - SLICE_S), seconds)
    tel = None
    if trace:
        from repro import obs

        tel = obs.configure(metrics=True, trace=True)
    compiles = CompileCounter()
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    win = tenant.window(dep, cfg, mix, load, seconds, span, slice_)
    in_window = compiles.count
    compiles.close()
    log(f"compiles in window: {in_window}")

    obs_snapshot = spans = None
    if tel is not None:
        from repro import obs

        obs_snapshot = tel.metrics.snapshot()
        spans = span_stats(tel.tracer.trace_events())
        obs.disable()
    if win.late_s is not None and len(win.late_s):
        log(f"generator late: p50 {np.percentile(win.late_s, 50) * 1e3:.6f}"
            f" ms, p99 {np.percentile(win.late_s, 99) * 1e3:.6f} ms, max "
            f"{win.late_s.max() * 1e3:.6f} ms over {len(win.late_s)} "
            "requests")
    log(f"window: {win.window_s:.6f} s, {win.attempted} attempted, "
        f"{win.finished} finished, {win.items} items, {win.steps} router "
        f"steps, {win.calls} calls")

    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in used)
    dep.close()
    del dep
    gc.collect()

    red = None
    if log_dir is not None:
        red = trace_reduce_dir(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    values, note = tenant.check(cfg, params, load, win)
    log(f"check: {note}")
    limits = cfg["check"]["limits"]
    failed = win.attempted - win.finished
    checks = {name: {"value": values[name], "limit": float(limits[name])}
              for name in sorted(limits)}
    checks["failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    ctx = {"cell": cell, "config": cfg, "mix": mix, "chips": chips,
           "tenant": tenant, "setup_s": setup_s, "deploy_s": deploy_s,
           "window": win, "obs": obs_snapshot, "spans": spans,
           "trace": red, "peaks": peaks}
    kind_key = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in catalog.metrics_for(bench, cell["name"], kind_key):
        value = catalog.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result


def trace_reduce_dir(log_dir: str):
    from bench import loops, trace_reduce

    return trace_reduce.reduce_dir(log_dir, loops.HOST_SPANS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    bench = catalog.benchmark()
    cell = catalog.cell(bench, args.workload)
    cfg = catalog.config(cell["config"])
    mix = catalog.mix(cell["traffic"], cell["name"])
    tenant = catalog.tenant(cfg.get("tenant"))
    if cfg["chips"] != cell["chips"]:
        log(f"run: cell {cell['name']} asks for {cell['chips']} chips, "
            f"its configuration states {cfg['chips']}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"run: no repro package under {src}; nothing ran")
        return 1
    if src not in sys.path:
        sys.path.insert(0, src)
    configure_jax()
    try:
        devices = require_devices(cell["chips"])
    except NoAccelerator as e:
        log(f"run: {e}; nothing ran")
        return 1
    result = run_cell(bench, cell, cfg, mix, tenant, args.seed,
                      args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
