"""Mean duration of the fleet member's device-returning stream call
(``fleet.stream`` spans: stage, host-to-device, program), in ms, under
the closed bulk loop."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "fleet.stream")
