"""Per-tier matcher executable: device time of every executable run
other than the post stage's, per device window, in the traced interval.
The post stage runs once per device window, so its runs in the trace
count the windows exactly."""

SOURCE = "device_trace"
POST_STAGE = "eval_post"  # the post stage's executable: jit_eval_post_tiered(<hash>)


def read(ctx):
    busy, runs = ctx["trace"]["module_busy_s"], ctx["trace"]["module_runs"]
    windows = sum(n for name, n in runs.items() if POST_STAGE in name)
    if not windows:
        return None
    matcher = sum(s for name, s in busy.items() if POST_STAGE not in name)
    return 1e3 * matcher / windows
