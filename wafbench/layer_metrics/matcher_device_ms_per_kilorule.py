"""Per-tier matcher executable: what a rule costs on the device. The
matchers' device time per device window in the traced interval (as
``matcher_device_ms_per_window`` reads it: every executable run other
than the post stage's, over the post stage's runs), over the serving
engine's compiled rules in thousands (``/waf/v1/stats``
``automata.rules``, after warm-up). A program that does not count its
rules gives nothing to read."""

SOURCE = "device_trace"
POST_STAGE = "eval_post"  # the post stage's executable: jit_cko_eval_post_<shapes>


def read(ctx):
    rules = ctx["setup"].get("automata", {}).get("rules")
    busy, runs = ctx["trace"]["module_busy_s"], ctx["trace"]["module_runs"]
    windows = sum(n for name, n in runs.items() if POST_STAGE in name)
    if not rules or not windows:
        return None
    matcher = sum(s for name, s in busy.items() if POST_STAGE not in name)
    return 1e3 * matcher / windows / (rules / 1000.0)
