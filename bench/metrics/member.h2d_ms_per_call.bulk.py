"""Mean time of a fleet member call's placement of its items on the
device (``fleet.h2d`` spans, each waited on until the copy is done),
in ms, under the closed bulk loop."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "fleet.h2d")
