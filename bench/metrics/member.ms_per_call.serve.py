"""Mean duration of the fleet member's host-to-host stream call
(``fleet.stream_host`` spans: pad, device_put, the stream program,
the blocking read-back), in ms, under the open-loop router."""


def read(ctx):
    spans = ctx["spans"]
    if ctx["mix"]["loop"] != "open" or not spans:
        return None
    s = spans.get("fleet.stream_host")
    if not s or not s["count"]:
        return None
    return s["seconds"] / s["count"] * 1e3
