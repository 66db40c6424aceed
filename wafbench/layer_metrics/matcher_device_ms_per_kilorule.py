"""Per-tier matcher executable: what a rule costs on the device. The
matchers' device time per device window in the traced interval (as
``matcher_device_ms_per_window`` reads it: every executable run other
than the post stage's, over the post stage's runs, both over the
capture's whole windows), over the serving engine's compiled rules in
thousands (``/waf/v1/stats`` ``automata.rules``, after warm-up). A
program that does not count its rules gives nothing to read."""

from wafbench.layer_metrics._trace_windows import whole_windows

SOURCE = "device_trace"


def read(ctx):
    rules = ctx["setup"].get("automata", {}).get("rules")
    matcher, windows = whole_windows(ctx["trace"])
    if not rules or not windows:
        return None
    return 1e3 * matcher / windows / (rules / 1000.0)
