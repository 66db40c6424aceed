"""Readback/decode: value-cache insert, verdict decode and overrides,
per window (stage ``decode``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("decode",))
