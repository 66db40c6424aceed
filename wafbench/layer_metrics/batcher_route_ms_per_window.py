"""Lanes + batcher: the batcher's own Python around the engine, per
window: tenant -> engine, quarantine gate, verdict-cache probe and
fingerprints before it; stats, hooks and the future after (stages
``route`` + ``resolve``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("route", "resolve"))
