"""Lanes + batcher: the share of a window's wall (first request read
-> last reply handed over) that no stage covers: the boundaries between
stages that are stamped in different modules. A window's ``lane_wait``
enters as its requests' mean, which is its own first-read -> close where
one read delivered it whole (as the cells' bursts are)."""

from wafbench.layer_metrics._window_stages import PER_WINDOW, grew

SOURCE = "program_span"


def read(ctx):
    wall, windows = grew(ctx, "window_wall", "sum_s"), grew(ctx, "window_wall", "count")
    if not wall:
        return None
    staged = sum(grew(ctx, s, "sum_s") for s in PER_WINDOW)
    requests = grew(ctx, "lane_wait", "count")
    if requests:
        staged += grew(ctx, "lane_wait", "sum_s") * windows / requests
    return 100.0 * (1.0 - staged / wall)
