"""Mean duration of the fleet member's host-to-host stream call
(``fleet.stream_host`` spans), in ms, under the closed bulk loop."""


def read(ctx):
    spans = ctx["spans"]
    if ctx["mix"]["loop"] != "closed" or not spans:
        return None
    s = spans.get("fleet.stream_host")
    if not s or not s["count"]:
        return None
    return s["seconds"] / s["count"] * 1e3
