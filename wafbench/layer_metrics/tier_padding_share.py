"""Tiering of a window: share of the matchers' input bytes that was
padding, over the tiers launched in the window: 1 - real bytes /
(unique rows x width, as bucketed), from the cumulative ``tiering``
block of ``/waf/v1/stats``. A program without the block gives nothing
to read."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("tiering"), ctx["after"].get("tiering")
    if not a or not b:
        return None
    cells = b["cells"] - a["cells"]
    if not cells:
        return None
    return 100.0 * (1 - (b["real_bytes"] - a["real_bytes"]) / cells)
