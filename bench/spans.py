"""The per-call readers' arithmetic over the program's own spans
(``ctx["spans"]``: count and seconds by span name, from ``repro.obs``)."""


def mean_ms(ctx, name: str):
    """Mean duration of the ``name`` spans in ms, under the closed bulk
    loop; None where there are none (an untraced run, a program without
    the span, the open loop)."""
    spans = ctx["spans"]
    if ctx["mix"]["loop"] != "closed" or not spans:
        return None
    s = spans.get(name)
    if not s or not s["count"]:
        return None
    return s["seconds"] / s["count"] * 1e3
