"""Mean time of ``Deployment.stream`` putting the member's host result
back on the device, waited on until it is there
(``fleet.result_to_device`` spans), in ms, under the closed bulk
loop."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "fleet.result_to_device")
