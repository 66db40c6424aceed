"""What the readers of the window stage record share (not a metric):
the growth, over the measured window, of the cumulative ``stages`` block
of ``/waf/v1/stats`` (coraza_kubernetes_operator_tpu/observability/stages.py:
per stage and lane ``count``, ``sum_s`` and buckets). A program without
the block, as the commit before it, reads as None everywhere."""

# Every stage counted once per window; lane_wait is counted per request.
PER_WINDOW = ("queue_wait", "depth_wait", "route", "assemble", "tier_enqueue",
              "prefilter_wait", "prefilter_confirm", "post_enqueue", "inflight_wait",
              "readback_wait", "decode", "resolve", "loop_hop", "reply_write")


def grew(ctx, stage, key):
    """Growth of ``key`` (``count`` or ``sum_s``) of one stage, summed
    over its lanes; None where the program reports no stages."""
    before, after = ctx["before"].get("stages"), ctx["after"].get("stages")
    if before is None or after is None:
        return None

    def total(block):
        return sum(v[key] for lane, v in block.get(stage, {}).items() if lane != "aborted")

    return total(after) - total(before)


def ms_per_window(ctx, stages):
    """Mean milliseconds a window of the interval spent in ``stages``
    together: their seconds over the windows that ended (``window_wall``'s
    count), so that the per-window metrics add up to the window's wall."""
    windows = grew(ctx, "window_wall", "count")
    if not windows:
        return None
    return 1e3 * sum(grew(ctx, s, "sum_s") for s in stages) / windows
