"""Per-tier matcher executable, seen from the host: the time the
prefilter confirm is blocked on the matchers' output, per window (stage
``prefilter_wait``; None where the rule set has no prefiltered group)."""

from wafbench.layer_metrics._window_stages import grew, ms_per_window

SOURCE = "program_span"


def read(ctx):
    if not grew(ctx, "prefilter_wait", "count"):
        return None
    return ms_per_window(ctx, ("prefilter_wait",))
