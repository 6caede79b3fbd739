"""Share of its roofline that the stream program reaches on the device:
the stream calls that ran in the profiled slice, each worth the least
time a chip could take for its items (the larger of work-count FLOPs
over peak and work-count bytes over HBM bandwidth), over the device's
busy time in that slice (profiler trace)."""
import math

from bench import workcount


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    if ctx["mix"]["loop"] != "closed" or red is None or peaks is None:
        return None
    if not red["modules"] or red["busy_s"] <= 0:
        return None
    cfg = ctx["config"]
    calls = max(m["calls"] for m in red["modules"].values())
    items = math.ceil(int(ctx["mix"]["items_per_call"]) / ctx["chips"])
    t_min = workcount.min_seconds(cfg["dims"], items, cfg["weight_bytes"],
                                  peaks[cfg["peak"]],
                                  peaks["hbm_bytes_per_s"])
    return calls / red["devices"] * t_min / red["busy_s"] * 100.0
