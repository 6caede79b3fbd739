"""The process-wide telemetry switchboard.

One :class:`Telemetry` pair (metrics registry + tracer) is current per
process; every instrumented component resolves it through
:func:`current` at use time, so ``configure()`` lights up telemetry in
an already-running stack and ``disable()`` returns it to the shared
inert pair. The disabled path is ONE module attribute read and a bool
check per engine step — the "~zero-cost when off" contract the
``obs_overhead`` benchmark holds at ≥0.9× (it measures the *enabled*
cost; disabled is cheaper still).

:class:`StepRecorder` is the engine-step instrument: the scheduler
opens one per traced step, brackets the named phases (admit, dispatch,
device_step, gather, finish) with ``phase(...)``, and ``close()``
emits the step span plus per-phase histograms — the measured
scatter/compute/gather split ROADMAP item 4 is gated on.
``NULL_RECORDER`` is its inert twin for the un-traced path.

:func:`span` is the one span primitive: while telemetry is active it
records a Chrome ``"X"`` event AND opens a
``jax.profiler.TraceAnnotation`` of the same name around the region,
so the span also lands on the profiler's host plane, on the same clock
as the device's ``XLA Ops``. Step phases go through it too.
"""
from __future__ import annotations

import time
from typing import Optional

from repro.obs.metrics import DEFAULT_RESERVOIR, MetricsRegistry
from repro.obs.trace import Tracer


class Telemetry:
    __slots__ = ("metrics", "tracer")

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.metrics = metrics if metrics is not None else \
            MetricsRegistry(enabled=False)
        self.tracer = tracer if tracer is not None else \
            Tracer(enabled=False)

    @property
    def active(self) -> bool:
        return self.metrics.enabled or self.tracer.enabled


_DISABLED = Telemetry()
_CURRENT = _DISABLED


def current() -> Telemetry:
    """The process-wide telemetry pair (inert unless configured)."""
    return _CURRENT


def configure(*, metrics: bool = True, trace: bool = True,
              reservoir: int = DEFAULT_RESERVOIR,
              max_events: int = 500_000,
              pid: Optional[int] = None) -> Telemetry:
    """Install (and return) a live telemetry pair. ``pid`` tags every
    trace event with the host rank; when omitted it is taken from an
    already-initialized jax distributed runtime (never initializing
    jax from here — this module stays import-light so ``python -m
    repro.obs`` can pin XLA flags first)."""
    global _CURRENT
    if pid is None:
        pid = 0
        try:                                    # pragma: no cover
            import sys
            jax = sys.modules.get("jax")
            if jax is not None:
                pid = int(jax.process_index())
        except Exception:
            pid = 0
    tel = Telemetry(
        MetricsRegistry(enabled=metrics, reservoir=reservoir),
        Tracer(enabled=trace, pid=int(pid), max_events=max_events))
    _CURRENT = tel
    return tel


def disable() -> None:
    """Return the process to the shared inert pair."""
    global _CURRENT
    _CURRENT = _DISABLED


# ------------------------------------------------------------------- #
# spans: the tracer's event and the profiler's annotation, one region
# ------------------------------------------------------------------- #
def _profiler():
    """``jax.profiler``, imported on first traced use only (this
    package stays free of jax at import)."""
    import jax.profiler
    return jax.profiler


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "t0", "dur", "_ann")

    def __init__(self, tracer: Tracer, name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._ann = _profiler().TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        self.tracer.complete(self.name, self.t0, self.dur, tid=0,
                             cat=self.cat, args=self.args)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, *, cat: str, **args):
    """Context manager timing one region of the host's work.

    Inactive telemetry: the shared null context, nothing recorded and
    ``jax.profiler`` never touched. Active: one complete ``"X"`` event
    on the tracer (``args`` as its args; the body may add to
    ``.args``) inside a ``jax.profiler.TraceAnnotation`` of the same
    name, so a profiler trace places the region beside the device's
    operations."""
    tel = _CURRENT
    if not tel.active:
        return _NULL_SPAN
    return _Span(tel.tracer, name, cat, args)


# ------------------------------------------------------------------- #
# per-engine-step phase recording
# ------------------------------------------------------------------- #
class _Phase(_Span):
    __slots__ = ("rec",)

    def __init__(self, rec: "StepRecorder", name: str, args):
        super().__init__(rec.tel.tracer, name, "phase", args)
        self.rec = rec

    def __exit__(self, *exc):
        super().__exit__(*exc)
        phases = self.rec.phases
        phases[self.name] = phases.get(self.name, 0.0) + self.dur
        return False


class NullRecorder:
    """The inert recorder the un-traced step body runs against."""
    __slots__ = ()
    phases: dict = {}

    def phase(self, name: str, **args):
        return _NULL_SPAN


NULL_RECORDER = NullRecorder()


class StepRecorder:
    __slots__ = ("tel", "tags", "phases")

    def __init__(self, tel: Telemetry, tags: Optional[dict] = None):
        self.tel = tel
        self.tags = tags or {}
        self.phases: dict = {}

    def phase(self, name: str, **args):
        """Bracket one named phase of the step; re-entering a name
        accumulates (``device_step`` runs once per payload key)."""
        return _Phase(self, name, args)

    def close(self, t0: float, *, emitted: int, step: int,
              idle: bool = False) -> None:
        """Emit the enclosing step span + metrics. The span covers
        everything since ``t0``, so Σ phase durations ≈ step duration
        (the --selftest tolerance check)."""
        dur = time.perf_counter() - t0
        args = dict(self.tags)
        args["emitted"] = emitted
        if idle:
            args["idle"] = True
        self.tel.tracer.complete("engine.step", t0, dur, tid=0,
                                 cat="step", args=args)
        m = self.tel.metrics
        m.histogram("engine.step_s").record(dur)
        for name, p_dur in self.phases.items():
            m.histogram("engine.phase_s", phase=name).record(p_dur)
