"""Host time per router step that is not the member call: the sum of
``engine.step_s`` less the sum of ``engine.phase_s{phase=device_step}``
over the window's steps, per step, in ms (repro.obs spans)."""


def read(ctx):
    snap = ctx["obs"]
    if not snap:
        return None
    hists = snap["histograms"]
    step = hists.get("engine.step_s")
    dev = hists.get("engine.phase_s|phase=device_step")
    if not step or not step["count"] or dev is None:
        return None
    return (step["sum"] - dev["sum"]) / step["count"] * 1e3
