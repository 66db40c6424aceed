"""Device: share of the traced interval in which no operation ran on the device."""

SOURCE = "device_trace"


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t["window_s"] else None
