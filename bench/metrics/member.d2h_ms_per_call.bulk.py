"""Mean time of a fleet member call's read-back of its outputs to the
host, pad rows dropped (``fleet.d2h`` spans), in ms, under the closed
bulk loop."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "fleet.d2h")
