"""Entry point: specs, residency check and the enqueue of every matcher
and of the post stage, per window (stages ``tier_enqueue`` +
``post_enqueue``)."""

from wafbench.layer_metrics._window_stages import ms_per_window

SOURCE = "program_span"


def read(ctx):
    return ms_per_window(ctx, ("tier_enqueue", "post_enqueue"))
