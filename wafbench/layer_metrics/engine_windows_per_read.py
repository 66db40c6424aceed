"""Lanes + batcher: blob windows closed per socket read that delivered
a request into one: one per resident engine and lane the read touched.
Growth of two frontend counters over the window; a program without them
reports nothing."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("frontend", {}), ctx["after"].get("frontend", {})
    if "window_reads_total" not in a or "window_reads_total" not in b:
        return None
    reads = b["window_reads_total"] - a["window_reads_total"]
    windows = b["blob_windows_total"] - a["blob_windows_total"]
    return windows / reads if reads else None
