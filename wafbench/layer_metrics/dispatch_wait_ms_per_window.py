"""Lanes + batcher: what a window waits for its turn, per window: in
the lane's queue, for an in-flight slot, and for the collector (stages
``queue_wait`` + ``depth_wait`` + ``inflight_wait``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("queue_wait", "depth_wait", "inflight_wait"))
