"""Lanes + batcher: the batcher's own median of its host stage (a host
clock from "tensors ready" to "post enqueued"), as it stands after the window."""

SOURCE = "program_span"


def read(ctx):
    return ctx["after"]["batcher"].get("p50_host_stage_ms")
