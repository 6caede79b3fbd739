"""Share of the latent cache's ring that the decode step's attention
reads: 100 x ``lm.attn_slots_read`` over ``lm.attn_slots_ring``
(repro.obs counters, per decode step summed over the layers: the
slots of each lane's ring the step's attention read, and the ring's)."""


def read(ctx):
    snap = ctx["obs"]
    if not snap:
        return None
    counters = snap["counters"]
    ring = counters.get("lm.attn_slots_ring")
    if not ring:
        return None
    return 100.0 * counters.get("lm.attn_slots_read", 0) / ring
