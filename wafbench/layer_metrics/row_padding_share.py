"""Tiering of a window: share of the matchers' rows that was padding,
over the tiers of the window: 1 - unique rows to match / rows as
bucketed, growth of ``tiering.rows`` over growth of
``tiering.rows_padded`` (``/waf/v1/stats``, before and after the window).
``tier_padding_share`` is the same of bytes. A program without the
counters gives nothing to read."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("tiering", {}), ctx["after"].get("tiering", {})
    if "rows_padded" not in a or "rows_padded" not in b:
        return None
    padded = b["rows_padded"] - a["rows_padded"]
    if not padded:
        return None
    return 100.0 * (1 - (b["rows"] - a["rows"]) / padded)
