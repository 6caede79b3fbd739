"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What is read:

  * the slice: the host annotation ``bench.slice`` that the harness
    opens right after the profiler starts and closes right before it
    stops; everything is clipped to it;
  * per device plane (``/device:TPU:<n>``), the ``XLA Ops`` line: each
    event is one operation running on the device. Busy time is the
    union of their intervals, idle share is 1 minus busy over the
    slice;
  * the ``XLA Modules`` line: one event per execution of a compiled
    program, counted and timed by program name;
  * the host annotations the harness writes around its calls into the
    program (``router.step``, ``Deployment.stream``, ...): each idle gap
    on a device is put down to the innermost annotation open at its
    midpoint, so the longest gaps say what the host was doing.

Nothing here imports the program. ``reduce_profile`` works on the
parsed ``jax.profiler.ProfileData``; the interval arithmetic below it
works on plain lists so that it can be checked by hand.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SLICE = "bench.slice"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_ANNOTATION = "host:unannotated"
TOP = 10

Interval = Tuple[float, float]          # (start, end), seconds


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around a merged busy cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]],
              depth: int = 16) -> Dict[str, float]:
    """Seconds of idle gap by the innermost host span open at each
    gap's midpoint. Spans nest at most ``depth`` deep, so only that
    many spans starting before a midpoint are candidates."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    by_name: Dict[str, float] = collections.defaultdict(float)
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        inner = None
        i = bisect.bisect_right(starts, mid) - 1
        for name, ss, se in spans[max(0, i - depth + 1):i + 1]:
            if mid < se and (inner is None or
                             se - ss < inner[2] - inner[1]):
                inner = (name, ss, se)
        by_name[inner[0] if inner else NO_ANNOTATION] += e - s
    return dict(by_name)


def _top(d: Dict[str, float]) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            [:TOP]]


def reduce_events(slice_span: Interval,
                  device_ops: Dict[int, List[Tuple[str, float, float]]],
                  device_modules: Dict[int, List[Tuple[str, float, float]]],
                  host_spans: Sequence[Tuple[str, float, float]]) -> dict:
    """The device metrics of one slice, from plain event lists (name,
    start, end in seconds). Busy and idle are averaged over devices;
    ops and gaps are summed over them."""
    lo, hi = slice_span
    window = hi - lo
    busy_each, ops, gap_s = [], collections.defaultdict(float), {}
    modules: Dict[str, List[float]] = {}
    for dev, events in sorted(device_ops.items()):
        iv = clip([(s, e) for _, s, e in events], lo, hi)
        cover = union(iv)
        busy_each.append(sum(e - s for s, e in cover))
        for name, s, e in events:
            c = clip([(s, e)], lo, hi)
            if c:
                ops[name] += c[0][1] - c[0][0]
        for name, sec in attribute(gaps(cover, lo, hi), host_spans).items():
            gap_s[name] = gap_s.get(name, 0.0) + sec
    for dev, events in sorted(device_modules.items()):
        for name, s, e in events:
            if lo <= s < hi:
                m = modules.setdefault(name, [0, 0.0])
                m[0] += 1
                m[1] += min(e, hi) - s
    busy = sum(busy_each) / len(busy_each) if busy_each else 0.0
    return {"window_s": window, "busy_s": busy,
            "busy_s_each": busy_each, "devices": len(busy_each),
            "idle_share": 1.0 - busy / window if window > 0 else None,
            "modules": {k: {"calls": v[0], "seconds": v[1]}
                        for k, v in modules.items()},
            "device_ops": _top(ops), "idle_gaps": _top(gap_s)}


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce_profile(profile, host_names: Optional[Sequence[str]] = None
                   ) -> Optional[dict]:
    """``reduce_events`` over a ``jax.profiler.ProfileData``; None when
    the trace holds no slice or no device plane with operations."""
    slice_span = None
    host_spans: List[Tuple[str, float, float]] = []
    device_ops: Dict[int, list] = {}
    device_modules: Dict[int, list] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == SLICE:
                        slice_span = (s, e)
                    elif host_names is None or name in host_names:
                        host_spans.append((name, s, e))
        elif m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[dev] = _events(line)
                elif line.name == MODULES_LINE:
                    device_modules[dev] = _events(line)
    if slice_span is None or not any(device_ops.values()):
        return None
    return reduce_events(slice_span, device_ops, device_modules, host_spans)


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_dir(log_dir: str, host_names: Optional[Sequence[str]] = None
               ) -> Optional[dict]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), host_names)
