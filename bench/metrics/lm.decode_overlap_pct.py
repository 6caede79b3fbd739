"""Share of the LM member's decode steps dispatched while the previous
step was still running on the device: 100 x ``lm.decode_overlapped``
over ``lm.decode_dispatched`` (repro.obs counters). Below 100 the host,
not the device, sets the pace of those steps."""


def read(ctx):
    snap = ctx["obs"]
    if not snap:
        return None
    counters = snap["counters"]
    dispatched = counters.get("lm.decode_dispatched")
    if not dispatched:
        return None
    return 100.0 * counters.get("lm.decode_overlapped", 0) / dispatched
