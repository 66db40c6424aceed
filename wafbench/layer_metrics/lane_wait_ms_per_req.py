"""Lanes + batcher: from the socket read that delivered a request to
the close of its lane's window, per request, in the window (stage
``lane_wait``)."""

from wafbench.layer_metrics._window_stages import grew

SOURCE = "program_span"


def read(ctx):
    n = grew(ctx, "lane_wait", "count")
    return 1e3 * grew(ctx, "lane_wait", "sum_s") / n if n else None
