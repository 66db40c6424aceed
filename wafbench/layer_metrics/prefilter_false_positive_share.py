"""Prefilter confirm: share of the device prefilter's hits that the
exact host confirm cleared, in the window."""

SOURCE = "program_counter"


def read(ctx):
    a = ctx["before"]["automata"].get("prefilter")
    b = ctx["after"]["automata"].get("prefilter")
    if not a or not b:
        return None
    hits = b["hits"] - a["hits"]
    return 100.0 * (b["false_positives"] - a["false_positives"]) / hits if hits else None
