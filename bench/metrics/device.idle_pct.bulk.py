"""Share of the profiled slice in which no operation ran on the
device, averaged over the chips, under the closed bulk loop
(profiler trace)."""


def read(ctx):
    red = ctx["trace"]
    if ctx["mix"]["loop"] != "closed" or red is None:
        return None
    return red["idle_share"] * 100.0
