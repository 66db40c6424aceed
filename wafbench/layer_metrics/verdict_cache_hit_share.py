"""Answer-without-device paths, the repeat-traffic part alone: share of
the window's requests that the verdict cache or the in-window dedup
answered."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("verdict_cache"), ctx["after"].get("verdict_cache")
    if not a or not b or not ctx["attempted"]:
        return None
    repeats = (b["hits_total"] - a["hits_total"]
               + b["window_dedup_rows"] - a["window_dedup_rows"])
    return 100.0 * repeats / ctx["attempted"]
