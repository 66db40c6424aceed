"""Per-tier matcher executable: share of the window's device windows
whose matcher took the long DFA scan in place of the conv tier: growth
of ``tiering.long_scan_launches`` (launches of an executable whose
``seg_plan.path`` is ``long``) over growth of
``compile_cache.device_windows``, from ``/waf/v1/stats`` before and after
the window. 0 says every launch rode the MXU. A witness of the plan, not a
timing (``seg_conv_steps_per_launch``). A program without the counter
gives nothing to read."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("tiering", {}), ctx["after"].get("tiering", {})
    if "long_scan_launches" not in a or "long_scan_launches" not in b:
        return None
    windows = (ctx["after"]["compile_cache"]["device_windows"]
               - ctx["before"]["compile_cache"]["device_windows"])
    if not windows:
        return None
    return 100.0 * (b["long_scan_launches"] - a["long_scan_launches"]) / windows
