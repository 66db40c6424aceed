"""Native window assemble: body processors, extraction, transforms and
the tiering of the window's rows, per window (stage ``assemble``,
stamped around ``WafEngine.prepare{,_blob}``; on the split path, where
a window with a verdict-cache hit rides Python objects, the Python
tensorizer's share of it is in here too)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("assemble",))
