"""Share of its roofline that the LM decode step reaches on the device:
the decode calls that ran in the profiled slice, each worth the least
time a chip could take for one step of the cell (the tenant's
``min_call_seconds`` at the lanes' positions at the window's start:
the larger of FLOPs over peak and bytes over HBM bandwidth), over the
device's busy time in that slice (profiler trace)."""


def read(ctx):
    red, peaks, win = ctx["trace"], ctx["peaks"], ctx["window"]
    positions = getattr(win, "positions", None)
    if red is None or peaks is None or positions is None:
        return None
    if not red["modules"] or red["busy_s"] <= 0:
        return None
    calls = max(m["calls"] for m in red["modules"].values())
    t_min = ctx["tenant"].min_call_seconds(ctx["config"], ctx["mix"],
                                           ctx["chips"], peaks,
                                           positions=positions)
    return calls / red["devices"] * t_min / red["busy_s"] * 100.0
