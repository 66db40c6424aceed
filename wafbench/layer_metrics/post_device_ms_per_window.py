"""Post stage: device time of the post stage's executable per run (one
run per device window), in the traced interval. Its module name holds
``eval_post`` and no other executable's does."""

SOURCE = "device_trace"
POST_STAGE = "eval_post"


def read(ctx):
    busy, runs = ctx["trace"]["module_busy_s"], ctx["trace"]["module_runs"]
    windows = sum(n for name, n in runs.items() if POST_STAGE in name)
    if not windows:
        return None
    return 1e3 * sum(s for name, s in busy.items() if POST_STAGE in name) / windows
