"""Lanes + batcher: requests per device window, in the window."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"], ctx["after"]
    windows = b["compile_cache"]["device_windows"] - a["compile_cache"]["device_windows"]
    return (b["batcher"]["requests"] - a["batcher"]["requests"]) / windows if windows else None
