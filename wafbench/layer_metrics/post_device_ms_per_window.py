"""Post stage: device time of the post stage's executable per run (one
run per device window), in the traced interval. Its module name holds
``eval_post`` and no other executable's does."""

from wafbench.layer_metrics._trace_windows import POST_STAGE

SOURCE = "device_trace"


def read(ctx):
    busy, runs = ctx["trace"]["module_busy_s"], ctx["trace"]["module_runs"]
    windows = sum(n for name, n in runs.items() if POST_STAGE in name)
    if not windows:
        return None
    return 1e3 * sum(s for name, s in busy.items() if POST_STAGE in name) / windows
