"""repro.obs — unified telemetry for the serving stack.

One process-wide :class:`Telemetry` pair — a :class:`MetricsRegistry`
(counters, gauges, bounded-reservoir histograms with exact p50/p95/p99
on short runs) and a span :class:`Tracer` (Chrome/Perfetto trace-event
JSON) — threaded through the whole stack: engine steps split into
named phases (admit, dispatch, device_step, gather, finish),
per-request spans reusing the ``ItemRequestState`` stamps, chip
program/stream timing, HA membership changes and variability
recalibrations as instants on the same timeline.

Off by default and ~zero-cost while off::

    from repro import obs
    obs.configure()                       # light it up
    ...serve...
    obs.current().metrics.snapshot()      # counters/gauges/histograms
    obs.current().tracer.write("t.json")  # load in ui.perfetto.dev
    obs.disable()

``Deployment.metrics()`` / ``Deployment.trace(path)`` wrap the same
pair; cross-host, :func:`allgather_snapshots` + :func:`merge_snapshots`
roll every rank's registry into one fleet-wide view.

This package never imports jax at module scope, so ``python -m
repro.obs --selftest`` can pin simulated-device XLA flags first.
"""
from repro.obs.core import (NULL_RECORDER, NullRecorder, StepRecorder,
                            Telemetry, configure, current, disable, span)
from repro.obs.metrics import (DEFAULT_RESERVOIR, Counter, Gauge,
                               Histogram, MetricsRegistry, Reservoir,
                               merge_snapshots)
from repro.obs.trace import LANE_TID_BASE, Tracer

__all__ = [
    "Counter", "DEFAULT_RESERVOIR", "Gauge", "Histogram",
    "LANE_TID_BASE", "MetricsRegistry", "NULL_RECORDER",
    "NullRecorder", "Reservoir", "StepRecorder", "Telemetry",
    "Tracer", "allgather_snapshots", "configure", "current",
    "disable", "merge_snapshots", "span",
]


def allgather_snapshots(snapshot):
    """Lazy re-export of :func:`repro.obs.dist.allgather_snapshots`
    (keeps jax out of this package's import)."""
    from repro.obs.dist import allgather_snapshots as _ag
    return _ag(snapshot)
