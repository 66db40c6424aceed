"""Per-tier matcher executable: device time of every executable run
other than the post stage's, per device window, over the whole windows
of the traced interval (``_trace_windows.py``: the post stage runs once
per device window, so its runs count the windows exactly, and what the
capture cut at either edge is left out)."""

from wafbench.layer_metrics._trace_windows import whole_windows

SOURCE = "device_trace"


def read(ctx):
    matcher, windows = whole_windows(ctx["trace"])
    return 1e3 * matcher / windows if windows else None
