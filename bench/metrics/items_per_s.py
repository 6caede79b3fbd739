"""Items completed per second over the whole closed-loop window: every
item of every call, over all the time from the window's start to the
end of its last call (host clock)."""


def read(ctx):
    if ctx["mix"]["loop"] != "closed":
        return None
    win = ctx["window"]
    return win.items / win.window_s
