"""Share of the rows the grouped expert layer computed that carried a
token routed to a held expert: 100 x ``lm.expert_assignments`` over
``lm.expert_rows`` (repro.obs counters, read back with each decode
step's tokens)."""


def read(ctx):
    snap = ctx["obs"]
    if not snap:
        return None
    counters = snap["counters"]
    rows = counters.get("lm.expert_rows")
    if not rows:
        return None
    return 100.0 * counters.get("lm.expert_assignments", 0) / rows
