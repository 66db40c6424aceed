"""Ingest: seconds the frontend spent parsing, per request parsed, in the window."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"]["frontend"], ctx["after"]["frontend"]
    n = b["requests_total"] - a["requests_total"]
    return 1e6 * (b["parse_s"] - a["parse_s"]) / n if n else None
