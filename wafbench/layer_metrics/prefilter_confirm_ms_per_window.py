"""Prefilter confirm: unpack, host transforms, the exact-DFA walk and
the repack, per window (stage ``prefilter_confirm``; None where the rule
set has no prefiltered group)."""

from wafbench.layer_metrics._window_stages import grew, ms_per_window

SOURCE = "program_span"


def read(ctx):
    if not grew(ctx, "prefilter_confirm", "count"):
        return None
    return ms_per_window(ctx, ("prefilter_confirm",))
