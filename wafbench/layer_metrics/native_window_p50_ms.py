"""Native window assemble: the native library's median per window, as it stands after the window."""

SOURCE = "program_span"


def read(ctx):
    return ctx["after"]["native"].get("p50_window_ms")
