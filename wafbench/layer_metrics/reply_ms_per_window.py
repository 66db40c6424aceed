"""Ingest: from the window's future set on the collector thread to its
last reply handed to a transport, per window (stages ``loop_hop`` +
``reply_write``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("loop_hop", "reply_write"))
