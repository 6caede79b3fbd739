"""Share of its roofline that the stream program reaches on the device:
the stream calls that ran in the profiled slice, each worth the least
time a chip could take for one call of the cell (the tenant's work
count: the larger of FLOPs over peak and bytes over HBM bandwidth),
over the device's busy time in that slice (profiler trace)."""


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    if ctx["mix"]["loop"] != "closed" or red is None or peaks is None:
        return None
    if not red["modules"] or red["busy_s"] <= 0:
        return None
    calls = max(m["calls"] for m in red["modules"].values())
    t_min = ctx["tenant"].min_call_seconds(ctx["config"], ctx["mix"],
                                           ctx["chips"], peaks)
    return calls / red["devices"] * t_min / red["busy_s"] * 100.0
