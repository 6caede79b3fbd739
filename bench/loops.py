"""The measured window: the two ways a cell drives the program.

``open_loop`` is independent senders: each request is submitted at its
scheduled arrival through the deployment's one router and the router
is stepped (``Deployment.step``) while it has work. A request's
latency runs from its scheduled arrival to the router's ``t_done``, so
a late generator or a stalled step counts against it. After the
window, the requests that arrived in it are followed to completion, up
to ``drain_s``; one that does not finish has failed.

``closed_loop`` is one caller that waits for each reply: back-to-back
``Deployment.stream`` calls cycling through the host batches, each
ended by ``block_until_ready``. The rate counts every item of every
call and all the time from the window's start to the last call's end.

In a traced run the harness's calls into each layer sit in
``jax.profiler.TraceAnnotation`` spans, and ``Slice`` profiles the
last seconds of the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import trace_reduce

HOST_SPANS = ("bench.generator", "bench.wait", "router.step",
              "Deployment.stream")


def no_span(_name: str):
    return contextlib.nullcontext()


class Slice:
    """Runs the JAX profiler from ``start`` to ``stop`` seconds into a
    window (host clock; ``arm`` gives the window's start), with the
    ``bench.slice`` annotation marking exactly the profiled part.
    Without a ``log_dir`` it does nothing."""

    def __init__(self, log_dir: Optional[str] = None, start: float = 0.0,
                 stop: float = 0.0):
        self.log_dir, self.start, self.stop = log_dir, start, stop
        self.t_start = self.t_stop = 0.0
        self._ann = None
        self.done = log_dir is None

    def arm(self, t0: float) -> None:
        self.t_start, self.t_stop = t0 + self.start, t0 + self.stop

    def poll(self, now: float) -> None:
        if self.done:
            return
        import jax

        if self._ann is None and now >= self.t_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_reduce.SLICE)
            self._ann.__enter__()
        elif self._ann is not None and now >= self.t_stop:
            self.close()

    def close(self) -> None:
        if self.done:
            return
        self.done = True
        if self._ann is not None:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class Window:
    """What one measured window did."""
    window_s: float
    attempted: int
    finished: int
    items: int
    calls: int = 0
    steps: int = 0
    latency_s: Optional[np.ndarray] = None
    arrival_s: Optional[np.ndarray] = None     # scheduled, from t0
    late_s: Optional[np.ndarray] = None
    # (pool row indices, outputs) of every answer to be checked
    answers: List = dataclasses.field(default_factory=list)


def warm_open(dep, app: str, sched, lanes: int) -> None:
    """Run the router's one step shape to completion, twice over, on
    pool items: what the window will run, compiled before it."""
    for _ in range(2):
        for j in range(lanes + 1):
            dep.submit(app, sched.pool[j:j + 2])
        dep.run_until_drained()


def open_loop(dep, app: str, sched, seconds: float, drain_s: float,
              span: Callable = no_span,
              slice_: Optional[Slice] = None) -> Window:
    from repro.serving.engine import ItemRequest

    router = dep.router
    n_before = len(router.finished)
    steps_before = router.steps
    count = sched.requests
    index: Dict[int, int] = {}
    late = np.zeros(count)
    slice_ = slice_ or Slice()
    t0 = time.perf_counter()
    arrive = t0 + sched.t
    slice_.arm(t0)
    deadline = t0 + seconds + drain_s
    i = 0
    while True:
        now = time.perf_counter()
        slice_.poll(now)
        if i < count and arrive[i] <= now:
            with span("bench.generator"):
                while i < count and arrive[i] <= now:
                    s, n = sched.start[i], sched.n[i]
                    req = ItemRequest(uid=0, items=sched.pool[s:s + n],
                                      t_submit=arrive[i])
                    router.submit_app(app, req)
                    index[id(req)] = i
                    late[i] = now - arrive[i]
                    i += 1
        if router.queue or router.active:
            if now > deadline:
                break
            with span("router.step"):
                dep.step()
        elif i < count:
            with span("bench.wait"):
                while time.perf_counter() < arrive[i]:
                    pass
        else:
            break
    slice_.close()
    done = router.finished[n_before:]
    lat = np.empty(len(done))
    arr = np.empty(len(done))
    answers = []
    items = 0
    for k, st in enumerate(done):
        j = index[id(st.request)]
        lat[k] = st.t_done - arrive[j]
        arr[k] = sched.t[j]
        s, n = sched.start[j], sched.n[j]
        answers.append((np.arange(s, s + n), st.result))
        items += n
    return Window(window_s=seconds, attempted=count, finished=len(done),
                  items=items, steps=router.steps - steps_before,
                  latency_s=lat, arrival_s=arr, late_s=late,
                  answers=answers)


def warm_closed(dep, app: str, batches: np.ndarray) -> None:
    import jax

    for _ in range(2):
        jax.block_until_ready(dep.stream(app, batches[0]))


def closed_loop(dep, app: str, batches: np.ndarray, seconds: float,
                keep: frozenset, span: Callable = no_span,
                slice_: Optional[Slice] = None) -> Window:
    """``keep``: call indices whose outputs are checked, besides the
    last call of every batch."""
    import jax

    slice_ = slice_ or Slice()
    nb, per = batches.shape[0], batches.shape[1]
    kept, last = {}, {}
    t0 = time.perf_counter()
    slice_.arm(t0)
    t_end = t0 + seconds
    n = 0
    while True:
        b = n % nb
        with span("Deployment.stream"):
            y = jax.block_until_ready(dep.stream(app, batches[b]))
        if n in keep:
            kept[n] = (b, y)
        last[b] = y
        n += 1
        now = time.perf_counter()
        slice_.poll(now)
        if now >= t_end:
            break
    slice_.close()
    answers = [(np.arange(b * per, (b + 1) * per), np.asarray(y))
               for b, y in list(kept.values()) + list(last.items())]
    return Window(window_s=now - t0, attempted=n, finished=n,
                  items=n * per, calls=n, answers=answers)
