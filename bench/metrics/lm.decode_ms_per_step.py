"""Mean time of the LM member's batched decode step, from dispatch to
the step's tokens on the host (``lm.decode`` spans), in ms."""


def read(ctx):
    spans = ctx["spans"]
    s = spans.get("lm.decode") if spans else None
    if not s or not s["count"]:
        return None
    return s["seconds"] / s["count"] * 1e3
