"""Mean time of a fleet member call's stream program, from dispatch to
done (``fleet.program`` spans), in ms, under the closed bulk loop."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "fleet.program")
