"""Readback/decode: the collector blocked in the window's device_get,
per window (stage ``readback_wait``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("readback_wait",))
