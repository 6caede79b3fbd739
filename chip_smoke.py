"""Run the serving path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip fleet phase only

One process, the user's entry points, no child processes. With one chip
it deploys the paper's streamable apps at their published widths —
``deep`` (784-200-100-10) on memristor cores, ``object`` (3072-100-10)
on digital cores, ``ocr`` (2500-60-26) on memristor cores — next to the
LM tenant (``qwen1p5_0p5b.reduced_serving()``), all through
``deploy()`` on one ``"chip"`` mesh, and runs these phases:

  * ``Deployment.stream`` of a 4096-item batch per app, on the einsum
    path and on the Pallas kernel path (which must lower to Mosaic);
  * a closed-loop serve of a few hundred requests through the one
    ``MultiAppRouter``, with the LM tenant decoding alongside, whose
    per-app stats must roll up exactly to the fleet row;
  * one live ``reprogram`` that runs zero compile passes;
  * the LM tenant's logits against the dense transformer.

With ``--chips 4`` it deploys ``deep`` across four chips instead, checks
that the plan sits on four distinct devices, and compares the fleet's
stream and routed outputs with the same app on one chip.

Every output is compared with an independent dense float32 reference
computed under ``jax.default_matmul_precision("highest")``. Weights are
random (``mlp_init`` from a seed) and inputs are 8-bit sensor codes made
on the device from a seed. Timings are printed as information only;
they are not benchmark numbers. The last stdout line is a JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU. Anything else exits non-zero.

The persistent compile cache goes to ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and to ``<repo>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BATCH = 4096                  # items per one-shot stream, per app
SENSOR_APPS = (("deep", "memristor"), ("object", "digital"),
               ("ocr", "memristor"))
SERVE_REQUESTS = 100          # routed requests per sensor app
ITEMS_PER_REQUEST = 4
LM_REQUESTS = 4
LM_PROMPT_LEN = 8
LM_NEW_TOKENS = 8

# --- tolerances, each with its reason -------------------------------- #
# The stream runs its f32 contractions at HIGHEST precision (several
# bf16 passes, close to f32), in another summation order than the
# reference: over at most 3072 products that is ~1e-6 relative. One
# bf16 pass would be ~1e-3 and fail this bound.
STREAM_RTOL = 1e-4
# Threshold activations turn such a difference into a ±1 flip when a
# pre-activation lies that close to zero, and one flip moves the output
# by a whole weight row. Rows with a reference pre-activation within
# THRESHOLD_MARGIN·max|z| of zero are "near threshold": left out of the
# float comparison, counted, and bounded in share.
THRESHOLD_MARGIN = 1e-5
MAX_NEAR_SHARE = 0.02
# The class (argmax) may differ on near-threshold rows and where two
# outputs lie within rounding of each other; nowhere else.
MIN_CLASS_AGREEMENT = 1.0 - MAX_NEAR_SHARE
# The LM's host glue (attention, norms, head) runs its f32 matmuls at
# the TPU's default precision, one bf16 pass with operands rounded to
# 8 significant bits (~4e-3 relative each), through 3 layers. Under
# default_matmul_precision("highest") the mapped LM must instead agree
# with the dense forward to summation order (LM_RTOL_HIGHEST).
LM_RTOL_DEFAULT = 5e-2
LM_RTOL_HIGHEST = 1e-4


def _setup_path_and_cache() -> None:
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no repro package under {src}; run this "
                 "script from a checkout of the repository")
    sys.path.insert(0, src)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


# --------------------------------------------------------------------- #
# the independent reference
# --------------------------------------------------------------------- #
def _dense_forward(plan, x):
    """Plain dense forward over a programmed plan: each crossbar layer's
    tile grid is folded back into one (d_in, d_out) matrix of effective
    weights (Eq. 3: (σ⁺ − σ⁻)·scale, times its Fig. 11 combiner weight),
    each digital layer is the exact integer MAC of the quantized input
    and the int8 synapses. Returns the outputs and every layer's
    pre-activation."""
    import jax.numpy as jnp

    from repro.core.crossbar_layer import CrossbarParams

    h = x.astype(jnp.float32)
    pre = []
    for layer in plan:
        t = layer.tiles
        if isinstance(t, CrossbarParams):
            R, C, rows, cols = t.gp.shape
            w = (t.gp - t.gn) * t.scale[:, :, None, :]
            if layer.levels:
                if len(layer.levels) != 1:
                    raise NotImplementedError("multi-level combiner")
                w = w * layer.combine[0][:R, None, None, None]
            w = w.transpose(0, 2, 1, 3).reshape(R * rows, C * cols)
            z = h @ w[:t.d_in, :t.d_out] + layer.bias
        else:
            # the digital core's DAC: [-1, 1] in 2^bits − 1 steps
            xq = jnp.clip(jnp.round((h + 1.0) / t.step), 0,
                          2.0 ** t.bits - 1.0)
            acc = jnp.dot(xq.astype(jnp.int32), t.wq.astype(jnp.int32))
            z = acc.astype(jnp.float32) * t.scale + t.offset + layer.bias
        pre.append(z)
        if layer.activation == "threshold":
            h = jnp.where(z >= 0, 1.0, -1.0)
        elif layer.activation == "linear":
            h = z
        else:
            raise NotImplementedError(layer.activation)
    return h, pre


def reference(plan, x):
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(_dense_forward)(plan, x)


def compare(y, ref, pre, plan) -> dict:
    """Stream output vs reference: float agreement on the rows away from
    every threshold, and class agreement on all rows."""
    import numpy as np

    y, ref = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    near = np.zeros(ref.shape[0], bool)
    for layer, z in zip(plan, pre):
        if layer.activation == "threshold":
            z = np.abs(np.asarray(z))
            near |= np.any(z <= THRESHOLD_MARGIN * z.max(), axis=1)
    keep = ~near
    rel = float(np.max(np.abs(y[keep] - ref[keep])) /
                max(float(np.max(np.abs(ref[keep]))), 1e-30)) \
        if keep.any() else float("inf")
    agree = float(np.mean(np.argmax(y, -1) == np.argmax(ref, -1)))
    ok = bool(y.shape == ref.shape and np.all(np.isfinite(y)) and
              rel <= STREAM_RTOL and near.mean() <= MAX_NEAR_SHARE and
              agree >= MIN_CLASS_AGREEMENT)
    return {"ok": ok, "rel": rel, "near_rows": int(near.sum()),
            "class_agreement": agree}


# --------------------------------------------------------------------- #
# inputs
# --------------------------------------------------------------------- #
def sensor_batch(key, n: int, d: int):
    """n items of d 8-bit sensor codes, mapped onto the [-1, 1] input
    range (made on the device)."""
    import jax
    import jax.numpy as jnp

    codes = jax.random.randint(key, (n, d), 0, 256)
    return (codes.astype(jnp.float32) * (2.0 / 255.0) - 1.0)


class SensorFeed:
    """A ``(seed, step)``-pure request feed for ``StreamSource``."""

    def __init__(self, d: int, n: int, seed: int):
        self.d, self.n, self.seed = d, n, seed

    def batch(self, step: int):
        import numpy as np

        rng = np.random.default_rng((self.seed, step))
        codes = rng.integers(0, 256, (self.n, self.d))
        return (codes * (2.0 / 255.0) - 1.0).astype(np.float32)


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #
class Run:
    """Phase bookkeeping: a phase that raises or fails a check fails the
    run, and the remaining phases still run."""

    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              f"{'  (' + detail + ')' if detail else ''}", flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        print(f"== {name}", flush=True)
        try:
            return fn(*args)
        except Exception:  # noqa: BLE001 — report, fail the run, go on
            traceback.print_exc()
            self.failed.append(name)
            return None


def timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def info(msg: str) -> None:
    print(f"  info: {msg}", flush=True)


def _cmp_detail(c: dict) -> str:
    return (f"rel {c['rel']:.3g} <= {STREAM_RTOL:g}, near-threshold "
            f"rows {c['near_rows']}, class agreement "
            f"{c['class_agreement']:.6f}")


def lowers_to_mosaic(chip, x) -> bool:
    """True when the kernel stream path of ``chip`` lowers to a Mosaic
    kernel on this backend (and not to interpret-mode jnp)."""
    import jax

    from repro.chip.compile import stream_pipeline

    txt = jax.jit(lambda p, v: stream_pipeline(
        p, v, use_kernel=True, replication=chip.replication)).lower(
            chip.plan, x).as_text()
    return "tpu_custom_call" in txt


def stream_phase(run: Run, dep, app: str, x) -> None:
    chip = dep.chip(app)
    ref, pre = reference(chip.plan, x)
    for use_kernel in (False, True):
        path = "kernel" if use_kernel else "einsum"
        y, t_first = timed(dep.stream, app, x, use_kernel=use_kernel)
        _, t_steady = timed(dep.stream, app, x, use_kernel=use_kernel)
        info(f"{app} {path} stream of {x.shape[0]} items: first call "
             f"{t_first:.3f} s (compile included), steady "
             f"{t_steady:.4f} s")
        c = compare(y, ref, pre, chip.plan)
        run.check(f"{app} {path} stream == dense reference", c["ok"],
                  _cmp_detail(c))
    run.check(f"{app} kernel path lowers to Mosaic",
              lowers_to_mosaic(chip, x[:8]))


def routed_check(run: Run, dep, finished, apps, label: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    for app in apps:
        states = [st for st in finished if st.request.key == app]
        xs = np.concatenate([st.request.items for st in states])
        ys = np.concatenate([st.result for st in states])
        chip = dep.chip(app)
        ref, pre = reference(chip.plan, jnp.asarray(xs))
        c = compare(ys, ref, pre, chip.plan)
        run.check(f"{label}: routed {app} outputs == dense reference",
                  c["ok"], f"{len(states)} requests, " + _cmp_detail(c))


def rollup_check(run: Run, dep, want_requests: int,
                 want_items: int) -> None:
    stats = dep.stats()
    roll = {f: sum(getattr(s, f) for s in stats.apps.values())
            for f in ("requests", "items", "rejected", "lanes")}
    exact = all(roll[f] == getattr(stats.fleet, f) for f in roll)
    run.check("per-app stats roll up exactly to the fleet row",
              exact and stats.fleet.requests == want_requests and
              stats.fleet.items == want_items,
              f"{roll}; expected {want_requests} requests, "
              f"{want_items} items")
    info(f"serve: {stats.fleet.steps} router steps, "
         f"{stats.fleet.items_per_second:.0f} items/s host clock, "
         f"latency p50 {stats.fleet.latency_s_p50 * 1e3:.2f} ms")


def serve_phase(run: Run, dep, lm: bool) -> None:
    import numpy as np

    from repro.configs.paper_apps import APPS
    from repro.fleet import StreamSource

    apps = [app for app, _ in SENSOR_APPS if app in dep.apps]
    sources = {app: StreamSource(
        SensorFeed(APPS[app].inputs_per_item, ITEMS_PER_REQUEST,
                   SEED + i), n_requests=SERVE_REQUESTS, capacity=8)
        for i, app in enumerate(apps)}
    n_lm = 0
    if lm:
        rng = np.random.default_rng(SEED)
        vocab = dep.router.members["lm"].cfg.vocab_size
        for _ in range(LM_REQUESTS):
            prompt = rng.integers(0, vocab, LM_PROMPT_LEN)
            n_lm += bool(dep.submit_tokens("lm", prompt,
                                           max_new_tokens=LM_NEW_TOKENS))
        run.check("LM requests admitted", n_lm == LM_REQUESTS)
    t0 = time.perf_counter()
    finished = dep.serve(sources)
    info(f"closed-loop serve: {time.perf_counter() - t0:.3f} s host clock "
         f"(first router steps compile)")
    run.check("every source drained and every request finished",
              all(s.exhausted for s in sources.values()) and
              len(finished) == len(apps) * SERVE_REQUESTS + n_lm,
              f"{len(finished)} finished")
    routed_check(run, dep, finished, apps, "serve")
    if lm:
        vocab = dep.router.members["lm"].cfg.vocab_size
        toks = dep.generated_tokens("lm")
        run.check("LM decoded through the router",
                  len(toks) == LM_REQUESTS and all(
                      len(t) == LM_NEW_TOKENS and
                      all(0 <= v < vocab for v in t)
                      for t in toks.values()),
                  f"{sum(map(len, toks.values()))} tokens")
    rollup_check(run, dep,
                 len(apps) * SERVE_REQUESTS + n_lm,
                 len(apps) * SERVE_REQUESTS * ITEMS_PER_REQUEST +
                 n_lm * LM_NEW_TOKENS)


def reprogram_phase(run: Run, dep, x) -> None:
    import jax
    import numpy as np

    from repro.chip import compile_count
    from repro.configs.paper_apps import APPS
    from repro.core.crossbar_layer import MLPSpec, mlp_init

    spec = MLPSpec(APPS["deep"].memristor_nets[0][1],
                   activation="threshold", out_activation="linear")
    before = np.asarray(dep.stream("deep", x))
    c0 = compile_count()
    _, t = timed(dep.reprogram, "deep",
                 mlp_init(jax.random.PRNGKey(SEED + 1), spec))
    info(f"reprogram deep: {t:.3f} s")
    run.check("reprogram runs zero compile passes",
              compile_count() == c0, f"delta {compile_count() - c0}")
    y = dep.stream("deep", x)
    chip = dep.chip("deep")
    ref, pre = reference(chip.plan, x)
    c = compare(y, ref, pre, chip.plan)
    run.check("reprogrammed deep == dense reference of the new weights",
              c["ok"] and not np.array_equal(np.asarray(y), before),
              _cmp_detail(c))


def lm_phase(run: Run, dep) -> None:
    import jax
    import numpy as np

    from repro.models import model as model_lib

    clm = dep.router.members["lm"].clm
    cfg, params = clm.cfg, clm.params
    rng = np.random.default_rng(SEED + 7)
    toks = rng.integers(0, cfg.vocab_size, (2, 9)).astype(np.int32)
    step = rng.integers(0, cfg.vocab_size, (2, 1)).astype(np.int32)
    pos = np.asarray([9, 9], np.int32)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    with jax.default_matmul_precision("highest"):
        d_logits, d_cache = jax.jit(
            lambda p, t: model_lib.prefill(cfg, p, {"tokens": t}))(
                params, toks)
        d_step, _ = jax.jit(lambda p, c, t, q: model_lib.decode_step(
            cfg, p, c, t, q))(params, d_cache, step, pos)
    for precision, rtol in (("default", LM_RTOL_DEFAULT),
                            ("highest", LM_RTOL_HIGHEST)):
        with (contextlib.nullcontext() if precision == "default" else
              jax.default_matmul_precision(precision)):
            (m_logits, m_cache), t = timed(clm.prefill, toks)
            (m_step, _), t2 = timed(clm.decode, m_cache, step, pos)
        info(f"LM {precision}: prefill {t:.3f} s, decode {t2:.3f} s "
             f"(first calls, compile included)")
        r1, r2 = rel(m_logits, d_logits), rel(m_step, d_step)
        run.check(f"LM logits ({precision} precision) == dense forward",
                  max(r1, r2) <= rtol,
                  f"prefill rel {r1:.3g}, decode rel {r2:.3g} <= {rtol:g}")


def one_chip(run: Run) -> None:
    import jax

    from repro.configs import qwen1p5_0p5b
    from repro.configs.paper_apps import APPS
    from repro.deploy import AppSpec, DeploymentSpec, deploy

    apps = [AppSpec(app, app, system=system, lanes_per_chip=8, seed=SEED)
            for app, system in SENSOR_APPS]
    apps.append(AppSpec("lm", qwen1p5_0p5b.reduced_serving(),
                        lanes_per_chip=LM_REQUESTS, cache_len=64,
                        seed=SEED))
    dep, t = timed(deploy, DeploymentSpec(apps=tuple(apps), n_chips=1))
    info(f"deploy() of {dep.apps}: {t:.3f} s")
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(SENSOR_APPS))
    xs = {app: sensor_batch(k, BATCH, APPS[app].inputs_per_item)
          for (app, _), k in zip(SENSOR_APPS, keys)}
    for app, _ in SENSOR_APPS:
        run.phase(f"stream {app}", stream_phase, run, dep, app, xs[app])
    run.phase("routed serve", serve_phase, run, dep, True)
    run.phase("reprogram", reprogram_phase, run, dep, xs["deep"])
    run.phase("LM logits", lm_phase, run, dep)
    dep.close()


def fleet(run: Run, n_chips: int) -> None:
    import jax
    import numpy as np

    from repro.configs.paper_apps import APPS
    from repro.deploy import AppSpec, deploy

    if not run.check(f"{n_chips} devices visible",
                     len(jax.devices()) >= n_chips,
                     f"{len(jax.devices())} devices"):
        return
    spec = AppSpec("deep", "deep", lanes_per_chip=8, seed=SEED)
    dep, t = timed(deploy, spec, n_chips=n_chips)
    info(f"deploy() deep on {n_chips} chips: {t:.3f} s")
    one = deploy(spec, n_chips=1)
    member = dep.router.members["deep"]
    leaves = jax.tree.leaves(member._plan)
    devices = {d for leaf in leaves for d in leaf.devices()}
    run.check(f"plan placed on {n_chips} distinct devices",
              len(devices) == n_chips and
              all(len(leaf.devices()) == n_chips for leaf in leaves),
              f"devices {sorted(d.id for d in devices)}")
    x = sensor_batch(jax.random.PRNGKey(SEED), BATCH,
                     APPS["deep"].inputs_per_item)
    chip = dep.chip("deep")
    ref, pre = reference(chip.plan, x)
    for use_kernel in (False, True):
        path = "kernel" if use_kernel else "einsum"
        y, t_first = timed(dep.stream, "deep", x, use_kernel=use_kernel)
        _, t_steady = timed(dep.stream, "deep", x, use_kernel=use_kernel)
        info(f"{n_chips}-chip {path} stream of {BATCH} items: first "
             f"{t_first:.3f} s, steady {t_steady:.4f} s")
        y1, t1 = timed(one.stream, "deep", x, use_kernel=use_kernel)
        _, t1s = timed(one.stream, "deep", x, use_kernel=use_kernel)
        info(f"1-chip {path} stream of {BATCH} items: first {t1:.3f} s,"
             f" steady {t1s:.4f} s")
        c = compare(y, ref, pre, chip.plan)
        run.check(f"{n_chips}-chip {path} stream == dense reference",
                  c["ok"], _cmp_detail(c))
        c1 = compare(y, y1, pre, chip.plan)
        run.check(f"{n_chips}-chip {path} stream == 1-chip stream",
                  c1["ok"], _cmp_detail(c1))
        info(f"{n_chips}-chip and 1-chip {path} streams bit-identical: "
             f"{np.array_equal(np.asarray(y), np.asarray(y1))}")
    run.phase("routed serve", serve_phase, run, dep, False)
    dep.close()
    one.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "four-chip fleet phase")
    args = ap.parse_args(argv)
    _setup_path_and_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "nothing ran", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"{len(jax.devices())} visible, jax {jax.__version__}",
          flush=True)
    run = Run()
    t0 = time.perf_counter()
    if args.chips == 1:
        run.phase("one chip", one_chip, run)
    else:
        run.phase(f"{args.chips}-chip fleet", fleet, run, args.chips)
    stats = dev.memory_stats() or {}
    info(f"total {time.perf_counter() - t0:.1f} s; device 0 "
         f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    if run.failed:
        print(f"chip_smoke: FAILED {run.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
