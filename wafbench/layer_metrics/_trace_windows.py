"""What the readers of the device trace share (not a metric): the
capture's whole device windows. A window ends with its post stage (the
one executable whose name holds ``eval_post``; it runs once a window), so
the whole windows are those between the end of the first post-stage run
in the capture and the end of the last, and the matchers' time is what
ran between the two. A capture starts and stops wherever the load
happens to be: counted over all of it, the window cut at either edge
gives a matcher run without its post stage or a post stage without its
matcher, one part in the number of windows either way (5% of a
21-window capture of ``crs-custom5k.ftw-salted-c1``: 49.8 and 52.5 ms
on one program, PR 37 and PR 39)."""

POST_STAGE = "eval_post"  # jit_cko_eval_post_<shapes>: no other executable's name holds it


def whole_windows(trace):
    """(seconds of every executable run other than the post stage's,
    device windows), both over the capture's whole windows; over the whole
    capture where a device plane holds fewer than two post-stage runs or
    the reduction kept no order of runs. (0.0, 0) where nothing ran."""
    planes = trace.get("module_events") or []
    ends = [[s + d for name, s, d in runs if POST_STAGE in name] for runs in planes]
    if planes and all(len(e) >= 2 for e in ends):
        between = [d for runs, e in zip(planes, ends) for name, s, d in runs
                   if POST_STAGE not in name and s >= e[0] and s + d <= e[-1]]
        windows = sum(len(e) - 1 for e in ends)
        # With two windows in flight the device interleaves their runs, and
        # an edge may fall between a matcher and its own post stage: the
        # runs between the edges are then one off a whole number a window,
        # and their time is scaled to that whole number.
        whole = round(len(between) / windows) * windows
        scale = whole / len(between) if between and abs(len(between) - whole) == 1 else 1.0
        return sum(between) * scale, windows
    busy, counted = trace["module_busy_s"], trace["module_runs"]
    windows = sum(n for name, n in counted.items() if POST_STAGE in name)
    return sum(s for name, s in busy.items() if POST_STAGE not in name), windows
