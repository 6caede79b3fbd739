"""Share of the chips' peak that the whole stream step reaches over the
window: the tenant's work count (FLOPs) of every item completed, over
the window seconds times the peak of the configuration's arithmetic
(bf16 for the float32 memristor path, int8 for the digital path) times
the chips."""


def read(ctx):
    if ctx["mix"]["loop"] != "closed" or ctx["peaks"] is None:
        return None
    cfg, win = ctx["config"], ctx["window"]
    peak = ctx["peaks"][cfg["peak"]] * ctx["chips"]
    return ctx["tenant"].flops(cfg, win.items) / \
        (win.window_s * peak) * 100.0
