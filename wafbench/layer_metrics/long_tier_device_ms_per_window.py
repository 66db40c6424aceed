"""Per-tier matcher executable, long tiers alone: device time of the
matcher executables ``jit_cko_match_<rows>x<width>`` whose width is
1,024 or more, per device window, in the traced interval (the post
stage runs once per device window, so its runs count the windows). A
program whose executables carry no shape in their name, or a trace
with no long tier in it, gives nothing to read."""

import re

SOURCE = "device_trace"
POST_STAGE = "eval_post"
LONG_WIDTH = 1024
_MATCHER = re.compile(r"cko_match_(\d+)x(\d+)")


def read(ctx):
    busy, runs = ctx["trace"].get("module_busy_s"), ctx["trace"].get("module_runs")
    if not busy or not runs:
        return None
    windows = sum(n for name, n in runs.items() if POST_STAGE in name)
    long_s = [s for name, s in busy.items()
              if (m := _MATCHER.search(name)) and int(m.group(2)) >= LONG_WIDTH]
    if not windows or not long_s:
        return None
    return 1e3 * sum(long_s) / windows
