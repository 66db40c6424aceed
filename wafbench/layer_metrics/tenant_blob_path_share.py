"""Ingest: share of the requests that carried a trusted tenant header
which rode a blob window (the rest took the per-request Python path).
Growth of two frontend counters over the window; a program without them,
or a window that sent no such request, reports nothing."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"].get("frontend", {}), ctx["after"].get("frontend", {})
    if "tenant_requests_total" not in a or "tenant_requests_total" not in b:
        return None
    named = b["tenant_requests_total"] - a["tenant_requests_total"]
    rode = b["tenant_blob_requests_total"] - a["tenant_blob_requests_total"]
    return 100.0 * rode / named if named else None
