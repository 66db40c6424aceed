"""Per-tier matcher executable: what a row costs on the device. The
matchers' device time per device window in the traced interval (as
``matcher_device_ms_per_window`` reads it: every executable run other
than the post stage's, over the post stage's runs, both over the
capture's whole windows), over the unique rows a window handed its
matchers before padding: growth of ``tiering.rows`` over growth of
``tiering.windows`` (``/waf/v1/stats``, before and after the window; a
cell's bursts are of one size, so the capture's windows launched what
the window's did). A program without the counter gives nothing to read."""

from wafbench.layer_metrics._trace_windows import whole_windows

SOURCE = "device_trace"


def read(ctx):
    a, b = ctx["before"].get("tiering", {}), ctx["after"].get("tiering", {})
    if "rows" not in a or "rows" not in b:
        return None
    rows, launched = b["rows"] - a["rows"], b["windows"] - a["windows"]
    matcher, windows = whole_windows(ctx["trace"])
    if not rows or not launched or not windows:
        return None
    return 1e6 * (matcher / windows) / (rows / launched)
