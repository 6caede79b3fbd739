"""Rate of the fleet member's host-to-device placement: the bytes
placed (``fleet.h2d_bytes`` counter, pad rows included) over the
seconds of the ``fleet.h2d`` spans, in GB/s, under the closed bulk
loop."""


def read(ctx):
    spans, snap = ctx["spans"], ctx["obs"]
    if ctx["mix"]["loop"] != "closed" or not spans or not snap:
        return None
    s = spans.get("fleet.h2d")
    placed = snap["counters"].get("fleet.h2d_bytes")
    if not s or s["seconds"] <= 0 or not placed:
        return None
    return placed / s["seconds"] / 1e9
